"""Load-aware execution-plan dispatch (paper §4.5, Fig 7).

MobiRNN's finding: the accelerator is shared (UI rendering on the mobile
GPU); under low/medium load offloading wins, under high load the CPU path is
faster — so the runtime must *sense load and choose*.  Here the same engine
drives serving-plan selection: each registered ``Plan`` carries a calibrated
base latency and a contention model; a pluggable ``LoadSensor`` supplies the
current load; ``Scheduler.choose`` picks the predicted-fastest plan and
``Scheduler.record`` folds observed latencies back into the calibration
(exponential moving average), so the crossover point is learned, not assumed.

The five LSTM execution plans it schedules (core/lstm.FORWARD_PLANS; see
that module's docstring for the full decision table):

* ``sequential`` / ``wavefront`` — XLA plans; the CPU-ish and
  diagonal-parallel baselines.
* ``fused_cell`` — per-cell Pallas kernel, T x L dispatches.  Wins in
  compute-bound regimes (H too large for VMEM-resident weights).
* ``fused_seq`` — sequence-resident Pallas kernel, ONE dispatch.  Wins in
  dispatch-bound regimes (the MobiRNN case: small models, long sequences).
  Its viability surface is the joint ``(block_b, time_chunk)`` table of
  kernels/lstm_seq.choose_batch_block: whole-T VMEM residency when it
  fits, double-buffered time streaming past that — so long T alone never
  disqualifies it; only a weight stack that blows the budget at
  ``(bm=1, tc=1)`` routes to ``fused_cell`` (wire the table in via
  ``Scheduler(viable=core/lstm.plan_viability(...))``, with
  ``train=True`` for training-step schedulers).
* ``fused_seq_q8`` — the sequence-resident plan over int8-quantized
  weights.  Same dispatch profile as ``fused_seq`` but its viability
  surface is the QUANTIZATION-AWARE budget table
  (``choose_batch_block(quantized=True)``: 1-byte weight stack + f32
  scales), so under tight VMEM it stays schedulable — whole-T resident,
  coarse-tiled — where the f32 plan must stream or drops out entirely;
  ``plan_viability`` sizes both surfaces so the per-tick Fig 7 choice sees
  the 4x smaller weight term.  Accuracy contract: int8 error band, not
  bit-equality — register it only where that band is acceptable.

The scheduler itself is FAMILY-GENERIC: ``viable=`` is just a predicate
over registered plan names, and core/plans.py is where families (lstm,
rwkv6) publish their plans, equivalence policies, and the VMEM working-set
models that build those predicates.  A multi-family scheduler combines
them with ``plans.scheduler_viability({scheduler_name: (family_plan,
family_predicate)})`` — e.g. ``accel_seq`` bound to the lstm family's
``fused_seq`` via ``lstm.plan_viability(...)`` and ``accel_wkv`` bound to
rwkv6's ``chunked_scan`` via ``plans.rwkv_viability(...)``; unbound names
(CPU fallbacks) stay always-viable.  Non-viable plans are never calibrated
and never chosen, exactly as for the single-family case.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Mapping, Protocol

from repro.obs import trace as trace_lib


class LoadSensor(Protocol):
    def load(self) -> float: ...          # in [0, 1]


@dataclasses.dataclass
class SyntheticLoadSensor:
    """Injected load — used by tests and the Fig 7 reproduction."""
    value: float = 0.0

    def load(self) -> float:
        return min(max(self.value, 0.0), 1.0)


class ProcLoadSensor:
    """Real sensor: normalised 1-minute loadavg (the /proc analogue of the
    paper's ADB / Adreno utilisation scripts)."""

    def __init__(self, n_cpus: int | None = None):
        import os
        self.n_cpus = n_cpus or os.cpu_count() or 1

    def load(self) -> float:
        import os
        try:
            return min(os.getloadavg()[0] / self.n_cpus, 1.0)
        except OSError:  # pragma: no cover
            return 0.0


@dataclasses.dataclass
class Plan:
    """An executable plan with a latency-vs-load contention model.

    ``shared``: whether the plan contends with the sensed load (the paper's
    GPU is shared with rendering; a dedicated CPU reservation is not).
    predicted(load) = base / max(eps, 1 - sensitivity * load)  when shared.
    """
    name: str
    fn: Callable
    base_latency_s: float = float("inf")
    shared: bool = True
    sensitivity: float = 1.0
    ema: float = 0.3

    def predicted(self, load: float) -> float:
        if not self.shared:
            return self.base_latency_s
        denom = max(1e-3, 1.0 - self.sensitivity * load)
        return self.base_latency_s / denom

    def observe(self, latency_s: float, load: float) -> None:
        # invert the contention model to update the base estimate
        if self.shared:
            latency_s = latency_s * max(1e-3, 1.0 - self.sensitivity * load)
        if self.base_latency_s == float("inf"):
            self.base_latency_s = latency_s
        else:
            self.base_latency_s = ((1 - self.ema) * self.base_latency_s
                                   + self.ema * latency_s)


@dataclasses.dataclass
class Decision:
    plan: str
    load: float
    predicted_s: dict[str, float]


class Scheduler:
    """``viable`` is an optional predicate ``plan_name -> bool`` filtering
    plans that cannot run at all on the current shapes (e.g. the
    sequence-resident kernel past its VMEM budget,
    kernels/lstm_seq.choose_batch_block -> None; see core/lstm.plan_viability
    for the wiring).  Non-viable plans are never calibrated and never chosen
    — calibrating one would waste a warm-up dispatch on a plan that only
    ever runs its fallback path, and choosing one would silently benchmark
    the fallback under the wrong name."""

    #: decision-history bound: the slot engine calls choose() once per
    #: decode tick for the engine's whole life, so an unbounded list would
    #: be a slow host-memory leak on the serving hot loop
    MAX_DECISIONS = 4096

    def __init__(self, sensor: LoadSensor,
                 viable: Callable[[str], bool] | None = None,
                 ladder: list[str] | None = None):
        import collections
        self.sensor = sensor
        self.viable = viable
        self.plans: dict[str, Plan] = {}
        self.decisions: collections.deque[Decision] = collections.deque(
            maxlen=self.MAX_DECISIONS)
        #: graceful-degradation ladder: plan names ordered most-expensive
        #: first.  ``level`` rungs are currently excluded from choose() —
        #: the serving watchdog steps it via degrade()/recover()
        self.ladder: list[str] = list(ladder or [])
        self.level: int = 0

    def register(self, plan: Plan) -> None:
        self.plans[plan.name] = plan

    def _demoted(self) -> set[str]:
        return set(self.ladder[:self.level])

    def _viable_plans(self, viable: Callable[[str], bool] | None
                      ) -> dict[str, Plan]:
        pred = self.viable if viable is None else viable
        demoted = self._demoted()
        out = {n: p for n, p in self.plans.items()
               if (pred is None or pred(n)) and n not in demoted}
        if not out:
            raise ValueError(
                f"no viable plan among {sorted(self.plans)} — the viability "
                "predicate (or degradation level "
                f"{self.level}/{self.ladder}) rejected every registered plan")
        return out

    def degrade(self, reason: str = "slo") -> bool:
        """Step one rung down the ladder: exclude the next (most expensive
        still-included) ladder plan from choose()/calibrate().  Refuses —
        returns False, state unchanged — when the ladder is spent or when
        stepping down would leave no viable plan; the engine must always
        have SOMETHING to run, degraded or not."""
        if self.level >= len(self.ladder):
            return False
        self.level += 1
        try:
            self._viable_plans(None)
        except ValueError:
            self.level -= 1
            return False
        tracer = trace_lib.get_tracer()
        if tracer.enabled:
            tracer.event("sched/degrade", level=self.level,
                         excluded=sorted(self._demoted()), reason=reason)
        return True

    def recover(self) -> bool:
        """Step one rung back up (re-admit the most recently demoted plan).
        Returns False at level 0."""
        if self.level == 0:
            return False
        self.level -= 1
        tracer = trace_lib.get_tracer()
        if tracer.enabled:
            tracer.event("sched/recover", level=self.level,
                         excluded=sorted(self._demoted()))
        return True

    @staticmethod
    def _blocked_call(plan: Plan, args, kwargs):
        import jax
        return jax.block_until_ready(plan.fn(*args, **kwargs))

    def calibrate(self, *args, repeats: int = 3,
                  viable: Callable[[str], bool] | None = None,
                  profile: Mapping[str, float] | None = None,
                  **kwargs) -> None:
        """Seed base latencies for each viable plan; non-viable plans keep
        base_latency_s = inf.

        A plan named in ``profile`` (plan name -> measured seconds, e.g.
        ``obs.profile.DeviceProfile.best_latencies(...)``) is seeded from
        the persisted measurement WITHOUT running — the measured-profiler
        path that replaces cold analytic estimates.  Every other viable
        plan is timed here: ONE untimed warmup call first (absorbing JIT
        compile — without it ``repeats=1`` records compile time as the
        base latency), then best-of-``repeats`` timed calls.
        """
        tracer = trace_lib.get_tracer()
        for plan in self._viable_plans(viable).values():
            if profile is not None and plan.name in profile:
                plan.base_latency_s = float(profile[plan.name])
                if tracer.enabled:
                    tracer.event("sched/calibrate", plan=plan.name,
                                 latency_s=plan.base_latency_s,
                                 source="profile")
                continue
            self._blocked_call(plan, args, kwargs)          # untimed warmup
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                self._blocked_call(plan, args, kwargs)
                best = min(best, time.perf_counter() - t0)
            plan.base_latency_s = best
            if tracer.enabled:
                tracer.event("sched/calibrate", plan=plan.name,
                             latency_s=best, source="measured")

    def choose(self, load: float | None = None,
               viable: Callable[[str], bool] | None = None) -> Decision:
        load = self.sensor.load() if load is None else load
        preds = {n: p.predicted(load)
                 for n, p in self._viable_plans(viable).items()}
        best = min(preds, key=preds.get)
        d = Decision(plan=best, load=load, predicted_s=preds)
        self.decisions.append(d)
        tracer = trace_lib.get_tracer()
        if tracer.enabled:
            tracer.event("sched/choose", plan=best, load=load,
                         predicted_s=preds[best], n_viable=len(preds))
        return d

    def run(self, *args, **kwargs):
        d = self.choose()
        plan = self.plans[d.plan]
        tracer = trace_lib.get_tracer()
        span = (tracer.span("sched/run", plan=d.plan, load=d.load)
                if tracer.enabled else trace_lib.NULL_SPAN)
        with span:
            t0 = time.perf_counter()
            out = self._blocked_call(plan, args, kwargs)
            latency = time.perf_counter() - t0
            span.set(latency_s=latency)
        plan.observe(latency, d.load)
        return out, d
