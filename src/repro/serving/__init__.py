from repro.serving.engine import EngineConfig, SlotEngine
from repro.serving.faults import (FaultInjector, FaultPlan, InjectedFault,
                                  LanePoison, PrefillFault, QueueFlood,
                                  SlowTick)
from repro.serving.slots import (FINISH_REASONS, FinishReason, PrefillLane,
                                 QueueFull, Request, RequestQueue, Result,
                                 Slot, SlotManager, TokenEvent,
                                 chunk_schedule)

__all__ = ["EngineConfig", "SlotEngine", "Request", "Result",
           "RequestQueue", "QueueFull", "Slot", "SlotManager", "TokenEvent",
           "PrefillLane", "chunk_schedule",
           "FinishReason", "FINISH_REASONS", "FaultPlan", "FaultInjector",
           "InjectedFault", "LanePoison", "PrefillFault", "SlowTick",
           "QueueFlood"]
