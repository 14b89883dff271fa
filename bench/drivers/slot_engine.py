"""Serving cells: a language model behind ``SlotEngine``, driven by the
cell's traffic through ``submit`` and ``stream``, as a client would.

Set-up draws the weights on the device from the seed (the configuration's
reference module says how), builds the engine, and serves one request
whose prompt walks every prefill segment shape the traffic can produce,
so every program the window runs is compiled (or loaded from the cache)
before it.  Then the traffic runs: a ramp, the measured window, and a
drain under the same arrivals until every request due in the window has
had its first token.

Every time is taken here, on ``time.perf_counter``, when the engine hands
the client a token: TTFT from the moment a request was due (queue wait
included), and the gaps between consecutive tokens of a request that end
inside the window.  After the run, a sample of the requests it finished,
drawn from the seed (the longest among them), is run through the
plain reference over prompt and served tokens, and the gaps by which the
served tokens' logits lie below the reference's best decide ``correct``:
their mean, against the configuration's limit (the widest gap and the
part beyond the served logits' own rounding are printed beside it).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import math
import time

import numpy as np

from harness import manifest, stats, traffic, xtrace
from harness import device as device_lib
from harness.device import describe

#: requests whose served tokens are compared with the reference per run
SAMPLE = 8


@dataclasses.dataclass
class Rec:
    """What the client saw of one request."""
    uid: int
    prompt: np.ndarray
    output_len: int
    due: float                       # perf_counter time it was due
    phase: str
    submit: float = 0.0
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    reason: str | None = None

    @property
    def ok(self) -> bool:
        return (self.done and self.reason in (None, "length")
                and len(self.tokens) == self.output_len)


def _model_config(cfgf: dict):
    from repro.configs import get_arch

    m = cfgf["model"]
    base = get_arch(cfgf["arch"])
    ssm = dataclasses.replace(base.ssm, head_dim=m["head_dim"],
                              lora_rank=m["lora_rank"])
    return dataclasses.replace(
        base, n_layers=m["n_layers"], d_model=m["d_model"], d_ff=m["d_ff"],
        vocab=m["vocab"], dtype=m["dtype"], ssm=ssm)


def build(cfgf: dict, seed: int):
    """Weights from the seed, in one jitted call on the device, and the
    engine over them.  Returns (reference module, weights, engine)."""
    import jax
    from repro.models import registry
    from repro.serving import EngineConfig, SlotEngine

    ref = manifest.reference(cfgf)
    model = registry.build(_model_config(cfgf))
    make = jax.jit(functools.partial(ref.make_weights, cfgf["model"],
                                     cfgf["init"]))
    w = make(traffic.key_for(seed))
    params = ref.to_program(w)
    want, _ = model.abstract_params()
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise ValueError("weights do not have the program's parameter "
                         f"layout: {jax.tree.structure(params)} vs "
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {b.shape}/{b.dtype} where the "
                             f"program holds {a.shape}/{a.dtype}")
    ec = cfgf["engine"]
    engine = SlotEngine(model, params, config=EngineConfig(
        n_slots=ec["n_slots"], max_seq=ec["max_seq"],
        queue_capacity=ec["queue_capacity"],
        prefill_chunk_len=ec["prefill_chunk_len"],
        prefill_lanes=ec["prefill_lanes"]))
    return ref, w, engine


def warm(engine, cfgf: dict, mix: dict, seed: int) -> None:
    """Serve one request through every segment shape of the mix's prompts
    (one full chunk, then each power of two below it that fits), a decode
    tick and a retirement."""
    from repro.serving import Request

    chunk = cfgf["engine"]["prefill_chunk_len"]
    pmax = mix["prompt"]["max"]
    length = min(pmax, 2 * chunk - 1)
    prompt = traffic.token_ids(seed, 10**9, length, cfgf["model"]["vocab"])
    engine.serve([Request(-1, prompt, max_new_tokens=3)])
    engine.take_finished()


class Loop:
    """The client side: submits requests, consumes the engine's stream,
    stamps every token, and starts and stops the profiler."""

    def __init__(self, engine, seed: int, vocab: int, trace_at=None,
                 log_dir: str | None = None, uid_base: int = 0):
        self.engine = engine
        self.uid_base = uid_base
        self.seed = seed
        self.vocab = vocab
        self.recs: dict[int, Rec] = {}
        self.outstanding: set[int] = set()
        self.gen = None
        self.trace_at = trace_at            # (start, stop) perf times
        self.log_dir = log_dir
        self.capture: xtrace.Capture | None = None
        self.waits: list[tuple[str, float, float]] = []
        self.lateness: list[float] = []

    def submit(self, uid: int, prompt_len: int, output_len: int,
               due: float, phase: str) -> None:
        from repro.serving import Request

        now = time.perf_counter()
        uid += self.uid_base
        prompt = traffic.token_ids(self.seed, uid, prompt_len, self.vocab)
        rec = Rec(uid, prompt, output_len, due, phase, submit=now)
        self.recs[uid] = rec
        self.lateness.append(now - due)
        self.engine.submit(Request(uid, prompt, max_new_tokens=output_len))
        self.outstanding.add(uid)

    def _profile(self, now: float) -> None:
        """Start the profiler when the trace window opens, stop it when
        it closes (once each)."""
        if self.trace_at is None:
            return
        start, stop = self.trace_at
        if self.capture is None and now >= start:
            self.capture = xtrace.Capture(self.log_dir)
            self.capture.__enter__()
        elif self.capture is not None and now >= stop:
            self._stop_profile()

    def _stop_profile(self) -> None:
        if self.capture is not None and not self.capture.t1:
            self.capture.__exit__(None, None, None)

    def step(self, next_due: float | None) -> Rec | None:
        """Advance by one streamed event, or sleep until ``next_due`` when
        nothing is in flight.  Returns the request the event was for."""
        now = time.perf_counter()
        self._profile(now)
        if self.gen is None:
            if not self.outstanding:
                if next_due is not None and next_due > now:
                    time.sleep(next_due - now)
                    self.waits.append(("bench/waiting-for-arrivals", now,
                                       time.perf_counter()))
                return None
            self.gen = self.engine.stream()
        ev = next(self.gen, None)
        if ev is None:
            self.gen = None
            return None
        t = time.perf_counter()
        rec = self.recs.get(ev.uid)
        if rec is None:
            return None
        if ev.token is not None:
            rec.times.append(t)
            rec.tokens.append(int(np.asarray(ev.token).reshape(-1)[0]))
        if ev.done:
            rec.done = True
            rec.reason = ev.finish_reason
            self.outstanding.discard(ev.uid)
            self.engine.take_finished()
        return rec

    def close(self) -> None:
        if self.gen is not None:
            self.gen.close()
            self.gen = None
        self._stop_profile()


def drive_open(loop: Loop, mix: dict, seconds: float, t_base: float):
    """Open loop: every arrival is submitted when due, whatever the engine
    is doing.  After the window the arrivals go on, and the run ends once
    every request due in the window has had its first token or has ended
    (or the drain runs out)."""
    arrivals = collections.deque(traffic.open_loop(mix, loop.seed, seconds))
    w0 = t_base + float(mix["ramp_s"])
    w1 = w0 + seconds
    stop = w1 + float(mix["drain_s"])
    left = {a.uid for a in arrivals if a.phase == "window"}
    while True:
        now = time.perf_counter()
        while arrivals and t_base + arrivals[0].due_s <= now:
            a = arrivals.popleft()
            loop.submit(a.uid, a.prompt_len, a.output_len,
                        t_base + a.due_s, a.phase)
        if (now >= w1 and not left) or now >= stop:
            break
        nxt = t_base + arrivals[0].due_s if arrivals else stop
        rec = loop.step(nxt)
        if rec is not None and (rec.times or rec.done):
            left.discard(rec.uid - loop.uid_base)
    loop.close()
    return w0, w1


def end_to_end(loop: Loop, w0: float, w1: float) -> dict:
    """TTFT from the moment it was due, over every request due in the
    window; every gap between consecutive tokens of a request that ends
    inside the window, over every request."""
    win = [r for r in loop.recs.values() if r.phase == "window"]
    ttft = [(r.times[0] - r.due) * 1e3 if r.times else math.inf
            for r in win]
    tbt = [(b - a) * 1e3 for r in loop.recs.values()
           for a, b in zip(r.times, r.times[1:]) if w0 <= b < w1]
    return {"ttft_p95_ms": stats.percentile(ttft, 95),
            "tbt_p95_ms": stats.percentile(tbt, 95) if tbt else math.inf}


def attempted_failed(loop: Loop) -> tuple[int, int]:
    """Requests due in the window, and those of them that ended other
    than by reaching their length or never had a first token."""
    win = [r for r in loop.recs.values() if r.phase == "window"]
    return len(win), sum((r.done and not r.ok) or not r.times for r in win)


def sample(loop: Loop, seed: int):
    """Requests the run finished, to compare: the longest, and others
    drawn from the seed, SAMPLE in all."""
    pool = sorted((r for r in loop.recs.values() if r.ok),
                  key=lambda r: r.uid)
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.prompt) + r.output_len, r.uid))
    rest = [r for r in pool if r is not longest]
    rng = traffic.rng_for(seed, 30)
    pick = rng.choice(len(rest), size=min(SAMPLE - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def bf16_ulp(x: np.ndarray) -> np.ndarray:
    """The spacing of bfloat16 numbers at ``x`` (8 significant bits)."""
    return np.exp2(np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def compare(ref, cfgf: dict, w, recs: list[Rec],
            control: str | None = None) -> dict:
    """The reference's gap at every served token of ``recs``: its best
    logit minus its logit for that token (with ``control``, for the token
    the reference computed at that lower precision puts first at the same
    position).  Returns the mean gap, the widest gap, the mean of what
    each gap exceeds half a bfloat16 ulp of the best logit by (the part
    the served logits' own rounding cannot explain), and how many tokens
    were compared."""
    seqs, tgts = [], []
    for r in recs:
        p = len(r.prompt)
        served = np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([r.prompt, served[:-1]]))
        t = np.full(p + len(served) - 1, -1, np.int32)
        t[p - 1:] = served
        tgts.append(t)
    if not seqs:
        return {"served_gap_mean": math.inf, "served_gap_widest": math.inf,
                "served_gap_excess": math.inf, "tokens_compared": 0}
    gaps, best = ref.logit_gaps(cfgf["model"], w, seqs, tgts,
                                block=int(cfgf["check"]["block"]),
                                control=control)
    g, b = np.concatenate(gaps), np.concatenate(best)
    excess = np.maximum(g - 0.5 * bf16_ulp(b), 0.0)
    return {"served_gap_mean": float(g.mean()),
            "served_gap_widest": float(g.max()),
            "served_gap_excess": float(excess.mean()),
            "tokens_compared": int(g.size)}


def host_spans(loop: Loop, records: list[dict], cap: xtrace.Capture):
    """Host intervals on the trace's clock: the engine's spans, its
    prefill chunks, and the client's waits."""
    out = []
    for r in records:
        if r.get("type") == "span":
            out.append((r["name"], cap.to_ns(r["ts"]),
                        cap.to_ns(r["ts"] + r["dur_s"])))
        elif r.get("name") == "serve/prefill_chunk":
            t = r["ts"]
            out.append((r["name"], cap.to_ns(t - r["attrs"]["chunk_s"]),
                        cap.to_ns(t)))
    for name, a, b in loop.waits:
        out.append((name, cap.to_ns(a), cap.to_ns(b)))
    return out


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log_dir: str) -> dict:
    from repro.obs import trace as obs_trace

    cfgf, mix = cell.config, cell.traffic
    ref, w, engine = build(cfgf, seed)
    warm(engine, cfgf, mix, seed)
    sink = None
    if trace:
        sink = obs_trace.ListSink()
        obs_trace.configure(sink=sink)
    t_base = time.perf_counter()
    trace_at = None
    if trace:
        # the window's last seconds, so that stopping the profiler, which
        # holds the host, falls after them
        b = t_base + float(mix["ramp_s"]) + seconds
        trace_at = (b - float(mix["trace_s"]), b)
    loop = Loop(engine, seed, cfgf["model"]["vocab"], trace_at, log_dir)
    compiles0 = device_lib.COUNTER.snapshot() if device_lib.COUNTER else None
    w0, w1 = drive_open(loop, mix, seconds, t_base)
    compiles = (device_lib.COUNTER.snapshot()[0] - compiles0[0]
                if compiles0 else None)
    if loop.capture is not None:
        loop.capture.load()
    setup_s = w0 - t_start
    device = describe(devices)
    attempted, failed = attempted_failed(loop)
    e2e = end_to_end(loop, w0, w1)
    e2e["setup_s"] = setup_s
    picked = sample(loop, seed)
    records = sink.records if sink is not None else []
    obs_trace.configure()
    loop.engine = None            # free the engine's state before the check
    del engine
    gc.collect()

    t_check = time.perf_counter()
    readings = compare(ref, cfgf, w, picked)
    readings["check_s"] = time.perf_counter() - t_check
    number = "served_gap_mean"
    value, limit = readings.pop(number), float(cfgf["check"][number])
    ok = bool(picked) and value <= limit and failed == 0
    out = {
        "correct": ok, "attempted": attempted, "failed": failed,
        "e2e": e2e, "device": device,
        "checks": [[number, value, limit], ["failed_requests", failed, 0]],
        "notes": {**readings, "compiles_while_serving": compiles,
                  "requests_compared": len(picked),
                  "generator_late_p99_ms":
                      stats.percentile(loop.lateness, 99) * 1e3
                      if loop.lateness else 0.0},
        "window": (w0, w1),
    }
    if trace and loop.capture is not None and loop.capture.trace is not None:
        cap = loop.capture
        out["trace"] = {
            "trace": cap.trace, "capture": cap, "records": records,
            "requests": loop.recs, "window": (w0, w1),
            "spans": host_spans(loop, records, cap),
            "model": cfgf["model"],
        }
    return out

