"""Family-generic execution-plan registry — MobiRNN's decision table for
EVERY recurrence family, not just the LSTM it was measured on.

The paper's levers (coarse state-resident work units, VMEM-budget-driven
tiling, the Fig 7 load-aware plan choice) are properties of the recurrence
SHAPE, so each family registers the same three things here:

* named **plans** (`PlanSpec`) — alternative executions of the same
  function, each with an **equivalence policy** (`EquivalencePolicy`):
  exact plans must match the family's oracle within per-dtype float
  tolerance; band plans (e.g. the int8-weight LSTM plan) within a
  documented error band — and, where fixed, the expected Pallas dispatch
  counts (`fwd_dispatches` / `train_dispatches`, the O(1)-in-T contract).
* a **working-set model** — the `choose_batch_block` / `choose_chunk`
  style budget function behind `Family.viability(...)`, which builds the
  `viable=` predicate the Fig 7 scheduler consumes (core/scheduler.py).
* **cases** — the family's deliberately awkward shapes.  The equivalence
  sweep in tests/test_plan_equivalence.py is GENERATED from this table
  (`value_sweep()` / `grad_sweep()`), so registering a family is all it
  takes for its plans to be swept plans x dtypes x odd-shapes x gradients.

Families registered here:

* ``lstm`` — the five plans of core/lstm.FORWARD_PLANS, unchanged (the
  registry serves them; core/lstm remains the source of truth for the plan
  functions and their names).  Viability delegates to
  ``lstm.plan_viability``.
* ``rwkv6`` — ``stepwise`` (the per-timestep oracle, models/rwkv.wkv_step
  scanned over T), ``chunked_xla`` (models/rwkv.wkv_chunked — the jnp scan
  the model shipped with, chunk clamped to the largest divisor), and
  ``chunked_scan`` (kernels/wkv6 — ONE Pallas dispatch forward, one
  reverse-sweep dispatch backward, any T).  Viability comes from
  ``kernels/wkv6.choose_chunk``.
* ``mamba`` — ``scan`` (the per-step ``lax.scan`` oracle,
  kernels/mamba_scan.mamba_scan_ref — the models/mamba recurrence) and
  ``fused_scan`` (kernels/mamba_scan.mamba_scan — ONE Pallas dispatch
  forward, one reverse-sweep dispatch backward, any T).  Viability comes
  from ``kernels/mamba_scan.choose_blocks``.

All three budget models and tile searches are thin tables over the shared
``core/tiling`` substrate — registering a family takes a working-set term
table and a ``fits`` closure, not a bespoke search.

All plan functions within a family share one calling convention;
``Family.apply`` / ``Family.grads`` run a plan and return a pytree of
arrays compared leaf-wise against the oracle's by the generated sweep.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class EquivalencePolicy(NamedTuple):
    """How close to the family oracle a plan must stay.

    ``kind`` is "exact" (same function, float tolerance) or "band"
    (documented approximation, e.g. the int8 error band).  ``tol`` maps
    dtype name -> assert_allclose kwargs for values; ``grad_tol`` the same
    for gradients — a dtype absent from ``grad_tol`` is excluded from the
    gradient sweep (e.g. the q8 plan's gradient contract is the separate
    STE test, not oracle agreement)."""
    kind: str
    tol: dict[str, dict]
    grad_tol: dict[str, dict] | None = None


class PlanSpec(NamedTuple):
    """One named execution plan of a family."""
    name: str
    fn: Callable
    policy: EquivalencePolicy
    #: expected Pallas dispatches for one forward / one value_and_grad —
    #: None means "not fixed" (e.g. per-cell plans scale with T*L).
    fwd_dispatches: int | None = None
    train_dispatches: int | None = None


class ProfileCandidate(NamedTuple):
    """One measurable point on a family's viable tiling surface —
    what ``Family.profile_hook`` yields and obs/profile.py times.

    ``fn`` is a ready-to-call (typically jitted) callable over ``args``;
    ``point`` holds the JSON-able tiling coordinates (``block_b`` /
    ``time_chunk`` / ``chunk``); ``model_s`` is the analytic roofline
    prediction the model-vs-measured report divides against."""
    family: str
    plan: str
    point: dict
    fn: Callable
    args: tuple
    model_s: float | None = None


class Case(NamedTuple):
    """One sweep shape.  ``heavy`` cases are slow-marked in the value
    sweep; gradient sweeps additionally treat ``heavy_grad`` (and every
    non-float32 dtype) as slow — mirroring the historical quick-loop
    weighting of the LSTM sweep."""
    label: str
    shape: tuple
    heavy: bool = False
    heavy_grad: bool = True


@dataclasses.dataclass(frozen=True)
class Family:
    """A recurrence family: plans + oracle + cases + budget model."""
    name: str
    oracle: str
    plans: dict[str, PlanSpec]
    cases: tuple[Case, ...]
    dtypes: tuple[str, ...]
    #: (case, dtype) -> opaque inputs object for apply/grads
    make_inputs: Callable[[Case, str], Any]
    #: (plan_name, inputs) -> pytree of arrays (compared leaf-wise)
    apply: Callable[[str, Any], Any]
    #: (plan_name, inputs) -> pytree of gradient arrays
    grads: Callable[[str, Any], Any]
    #: family-specific keyword signature; returns the Fig 7 ``viable=``
    #: predicate (plan name -> bool) from the VMEM working-set model
    viability: Callable[..., Callable[[str], bool]]
    #: measured-profiler hook: ``(vmem_budget=..., max_points=..., **shape
    #: overrides) -> list[ProfileCandidate]`` enumerating the viable
    #: tiling surface for obs/profile.profile_families to time; None means
    #: the family opts out of measured profiling
    profile_hook: Callable[..., list] | None = None

    def comparable_plans(self) -> list[str]:
        return [n for n in self.plans if n != self.oracle]

    def tol(self, plan: str, dtype: str) -> dict:
        return self.plans[plan].policy.tol[dtype]

    def grad_tol(self, plan: str, dtype: str) -> dict | None:
        gt = self.plans[plan].policy.grad_tol
        return None if gt is None else gt.get(dtype)


FAMILIES: dict[str, Family] = {}


def register_family(family: Family) -> Family:
    if family.oracle not in family.plans:
        raise ValueError(f"oracle {family.oracle!r} not among plans "
                         f"{list(family.plans)}")
    FAMILIES[family.name] = family
    return family


def get_family(name: str) -> Family:
    return FAMILIES[name]


# ---------------------------------------------------------------------------
# Sweep generation — the single source the equivalence tests parametrize on
# ---------------------------------------------------------------------------
class SweepCase(NamedTuple):
    family: str
    plan: str
    case: Case
    dtype: str
    heavy: bool

    @property
    def id(self) -> str:
        return f"{self.family}-{self.plan}-{self.case.label}-{self.dtype}"


def value_sweep() -> list[SweepCase]:
    """plans x cases x dtypes for every registered family (oracle
    excluded — it is the reference, not a claim)."""
    out = []
    for fam in FAMILIES.values():
        for plan in fam.comparable_plans():
            for case in fam.cases:
                for dtype in fam.dtypes:
                    if dtype not in fam.plans[plan].policy.tol:
                        continue
                    out.append(SweepCase(fam.name, plan, case, dtype,
                                         heavy=case.heavy))
    return out


def grad_sweep() -> list[SweepCase]:
    """Gradient sweep: only (plan, dtype) pairs whose policy carries a
    ``grad_tol`` — the training-story guarantee, generated per family."""
    out = []
    for fam in FAMILIES.values():
        for plan in fam.comparable_plans():
            for case in fam.cases:
                for dtype in fam.dtypes:
                    if fam.grad_tol(plan, dtype) is None:
                        continue
                    heavy = case.heavy_grad or dtype != "float32"
                    out.append(SweepCase(fam.name, plan, case, dtype, heavy))
    return out


# ---------------------------------------------------------------------------
# Scheduler glue — one predicate over many families
# ---------------------------------------------------------------------------
def scheduler_viability(bindings: dict[str, tuple[str, Callable[[str], bool]]]
                        ) -> Callable[[str], bool]:
    """Combine per-family viability predicates into the single
    ``Scheduler(viable=...)`` callable.

    ``bindings`` maps a SCHEDULER plan name to ``(family_plan_name,
    family_predicate)`` — benchmarks register e.g. ``accel_seq`` for the
    lstm family's ``fused_seq`` and ``accel_wkv`` for rwkv6's
    ``chunked_scan``; names not bound to any family stay always-viable
    (CPU fallbacks)."""
    def viable(plan_name: str) -> bool:
        bound = bindings.get(plan_name)
        if bound is None:
            return True
        family_plan, predicate = bound
        return predicate(family_plan)

    return viable


# ===========================================================================
# lstm family — FORWARD_PLANS served through the registry, names unchanged
# ===========================================================================
#: per-dtype tolerance of the exact LSTM plans vs forward_sequential
LSTM_TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
            "bfloat16": dict(rtol=5e-2, atol=5e-2)}
LSTM_GRAD_TOL = {"float32": dict(rtol=2e-4, atol=2e-5),
                 "bfloat16": dict(rtol=8e-2, atol=8e-2)}
#: THE documented int8 error band (ROADMAP §Quantization): per-output-
#: channel symmetric int8 bounds each dequantized weight within
#: max|w_col|/254 of f32, and the saturating LSTM nonlinearities keep the
#: recurrence from amplifying it — logits land within 5e-2 of the f32
#: plans at the paper shapes (measured headroom ~5x).
Q8_BAND = dict(rtol=5e-2, atol=5e-2)

_LSTM_EXACT = EquivalencePolicy("exact", LSTM_TOL, LSTM_GRAD_TOL)
#: the q8 plan: banded values, and NO oracle gradient contract — its
#: training guarantee is exact-math STE agreement (test_plan_equivalence's
#: Q8 section), not closeness to the f32 oracle's gradients.
_LSTM_Q8 = EquivalencePolicy("band",
                             {d: Q8_BAND for d in ("float32",)},
                             grad_tol=None)

#: (batch, seq_len, hidden, input_dim, n_layers) — none block-aligned
_LSTM_CASES = (
    Case("b3t7h48d9l2", (3, 7, 48, 9, 2), heavy_grad=False),  # canonical
    Case("b1t5h33d9l3", (1, 5, 33, 9, 3)),    # B=1, hidden not lane-aligned
    Case("b5t3h16d40l2", (5, 3, 16, 40, 2)),  # input_dim > hidden: P padding
)


def _lstm_make_inputs(case: Case, dtype: str):
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import lstm

    b, t, h, d, n_layers = case.shape
    cfg = dataclasses.replace(LSTMConfig(), hidden=h, input_dim=d,
                              n_layers=n_layers, seq_len=t, dtype=dtype)
    params = lstm.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, d), jnp.dtype(dtype))
    labels = jnp.arange(b) % cfg.n_classes
    return cfg, params, x, labels


def _lstm_apply(plan: str, inputs):
    from repro.core import lstm

    cfg, params, x, _ = inputs
    return lstm.FORWARD_PLANS[plan](params, x, cfg)


def _lstm_grads(plan: str, inputs):
    from repro.core import lstm

    cfg, params, x, labels = inputs
    _, g = jax.value_and_grad(
        lambda p: lstm.loss_fn(p, x, labels, cfg,
                               forward=lstm.FORWARD_PLANS[plan]))(params)
    return g


def _lstm_viability(*args, **kwargs):
    from repro.core import lstm

    return lstm.plan_viability(*args, **kwargs)


def _lstm_profile_candidates(*, vmem_budget: int | None = None,
                             max_points: int = 4, batch: int = 4,
                             seq_len: int = 48) -> list[ProfileCandidate]:
    """Measured-profiler candidates: jitted ``fused_seq`` dispatches over
    a deterministic slice of the viable ``(block_b, time_chunk)`` surface
    at the canonical MobiRNN layer shape — coarsest tilings first (whole-T
    residency, full batch), then finer time chunks and batch halves, each
    admitted only if ``working_set_bytes`` fits the budget.  ``model_s``
    is the two-term roofline of ``analysis.lstm_seq_stream_costs``."""
    import functools

    from repro import analysis
    from repro.configs.mobirnn_lstm import LSTMConfig
    from repro.core import factorization as fz
    from repro.core import lstm as lstm_lib
    from repro.kernels import lstm_seq as seq_lib

    cfg = LSTMConfig()
    budget = fz.DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    p = lstm_lib._plain_params(
        lstm_lib.init_params(jax.random.PRNGKey(0), cfg))
    w, b, p_width = seq_lib.stack_params(p["layers"], cfg.hidden)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (batch, seq_len, cfg.input_dim), jnp.float32)
    xp = seq_lib.pad_input(x, p_width)

    time_chunks: list[int | None] = [None]
    for t in (seq_len // 2, seq_len // 4):
        if 1 <= t < seq_len and t not in time_chunks:
            time_chunks.append(t)
    out: list[ProfileCandidate] = []
    for bm in sorted({batch, max(1, batch // 2)}, reverse=True):
        for tc in time_chunks:
            if len(out) >= max_points:
                return out
            ws = seq_lib.working_set_bytes(
                seq_len, cfg.n_layers, p_width, cfg.hidden, bm,
                time_chunk=tc)
            if ws > budget:
                continue
            fn = jax.jit(functools.partial(
                seq_lib.lstm_seq, block_b=bm, time_chunk=tc))
            costs = analysis.lstm_seq_stream_costs(
                seq_len, cfg.n_layers, p_width, cfg.hidden, batch, bm, tc)
            out.append(ProfileCandidate(
                "lstm", "fused_seq", {"block_b": bm, "time_chunk": tc},
                fn, (w, b, xp),
                model_s=max(costs["t_compute"], costs["t_memory"])))
    return out


def _build_lstm_family() -> Family:
    from repro.core import lstm

    specs: dict[str, PlanSpec] = {}
    for name, fn in lstm.FORWARD_PLANS.items():
        if name == "fused_seq_q8":
            spec = PlanSpec(name, fn, _LSTM_Q8,
                            fwd_dispatches=1, train_dispatches=2)
        elif name in ("fused_seq",):
            spec = PlanSpec(name, fn, _LSTM_EXACT,
                            fwd_dispatches=1, train_dispatches=2)
        else:
            spec = PlanSpec(name, fn, _LSTM_EXACT)
        specs[name] = spec
    return Family(
        name="lstm", oracle="sequential", plans=specs, cases=_LSTM_CASES,
        dtypes=("float32", "bfloat16"), make_inputs=_lstm_make_inputs,
        apply=_lstm_apply, grads=_lstm_grads, viability=_lstm_viability,
        profile_hook=_lstm_profile_candidates)


# ===========================================================================
# rwkv6 family — stepwise oracle, XLA chunked scan, fused Pallas chunked scan
# ===========================================================================
#: chunked-vs-stepwise agreement band (log-space chunk math reassociates
#: the decay products; same bound tests/test_properties.py measures)
RWKV_TOL = {"float32": dict(rtol=5e-4, atol=5e-4),
            "bfloat16": dict(rtol=6e-2, atol=6e-2)}
RWKV_GRAD_TOL = {"float32": dict(rtol=2e-3, atol=2e-3)}

_RWKV_EXACT = EquivalencePolicy("exact", RWKV_TOL, RWKV_GRAD_TOL)

#: (B, T, H, dk, dv, chunk) — C=1, C=T, non-dividing T, chunk > T all on
#: the table, so the padding and clamping paths are part of the sweep
_RWKV_CASES = (
    Case("c8t24", (2, 24, 2, 8, 8, 8)),                     # C | T
    Case("c1", (2, 12, 2, 8, 8, 1), heavy_grad=False),      # C=1: per-step
    Case("cT", (1, 16, 2, 8, 8, 16)),                       # C=T: one chunk
    Case("oddT", (2, 23, 2, 8, 8, 8), heavy_grad=False),    # pad path
    Case("cgtT", (1, 7, 2, 8, 10, 32)),                     # clamp, dk != dv
    Case("long", (2, 96, 2, 16, 16, 16), heavy=True),
)


def _rwkv_make_inputs(case: Case, dtype: str):
    import zlib

    B, T, H, dk, dv, chunk = case.shape
    dt = jnp.dtype(dtype)
    seed = zlib.crc32(case.label.encode()) % (2 ** 31)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = jax.random.normal(ks[0], (B, T, H, dk), dt)
    k = jax.random.normal(ks[1], (B, T, H, dk), dt)
    v = jax.random.normal(ks[2], (B, T, H, dv), dt)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, dk)))     # f32, <= 0
    u = jax.random.normal(ks[4], (H, dk))
    state = jax.random.normal(ks[5], (B, H, dk, dv)) * 0.3       # f32
    return (r, k, v, logw, u, state), chunk


def _rwkv_stepwise(r, k, v, logw, u, state, *, chunk):
    """Per-timestep oracle: models/rwkv.wkv_step scanned over T — the
    fine-grained 'CUDA-style' plan every chunked plan must reproduce."""
    from repro.models import rwkv as rwkv_lib

    def step(s, xs):
        out, s = rwkv_lib.wkv_step(*xs, u, s)
        return s, out

    swap = lambda a: jnp.swapaxes(a, 0, 1)           # (B,T,H,*) -> (T,B,H,*)
    state, outs = jax.lax.scan(
        step, state.astype(jnp.float32), tuple(map(swap, (r, k, v, logw))))
    return swap(outs).astype(v.dtype), state


def _rwkv_chunked_xla(r, k, v, logw, u, state, *, chunk):
    """models/rwkv.wkv_chunked with the model's divisor clamp — the jnp
    lax.scan plan (O(T/C) fused-loop iterations, no Pallas)."""
    from repro.models import rwkv as rwkv_lib

    S = r.shape[1]
    c = max(1, min(chunk, S))
    while S % c:              # largest divisor of S not above the target
        c -= 1
    out, state = rwkv_lib.wkv_chunked(r, k, v, logw, u, state, c)
    return out.astype(v.dtype), state


def _rwkv_chunked_scan(r, k, v, logw, u, state, *, chunk, bh_tile=1,
                       bwd=None, interpret=None):
    """kernels/wkv6 Pallas plan: model layout (B,S,H,*) folded to the
    kernel's (B*H, S, *), u broadcast per batch-head (its VJP sums the
    cotangent back over B), any T via the kernel's identity zero-pad."""
    from repro.kernels import wkv6 as wkv6_lib

    if bwd is None:
        bwd = wkv6_lib.FUSED_BWD
    B, S, H, dk = r.shape
    dv = v.shape[-1]

    def merge(a):
        return jnp.swapaxes(a, 1, 2).reshape(B * H, S, a.shape[-1])

    ub = jnp.broadcast_to(u[None], (B, H, dk)).reshape(B * H, dk)
    out, s_out = wkv6_lib.wkv6(
        merge(r), merge(k), merge(v), merge(logw), ub,
        state.reshape(B * H, dk, dv), chunk=chunk, bh_tile=bh_tile,
        bwd=bwd, interpret=interpret)
    out = jnp.swapaxes(out.reshape(B, H, S, dv), 1, 2)
    return out, s_out.reshape(B, H, dk, dv)


RWKV_PLANS: dict[str, Callable] = {
    "stepwise": _rwkv_stepwise,
    "chunked_xla": _rwkv_chunked_xla,
    "chunked_scan": _rwkv_chunked_scan,
}


def _rwkv_apply(plan: str, inputs):
    args, chunk = inputs
    return RWKV_PLANS[plan](*args, chunk=chunk)


def _rwkv_grads(plan: str, inputs):
    (r, k, v, logw, u, state), chunk = inputs

    def loss(r, k, v, logw, u, state):
        out, s = RWKV_PLANS[plan](r, k, v, logw, u, state, chunk=chunk)
        return (jnp.sum(jnp.tanh(out.astype(jnp.float32)))
                + 0.5 * jnp.sum(s * s))

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(
        r, k, v, logw, u, state)


def _plan_viable(blocks, gated_plan_names: tuple[str, ...]
                 ) -> Callable[[str], bool]:
    """The shared viability closure every family factory returns: the
    accelerator plan(s) in ``gated_plan_names`` are real plans only when
    the family's tiling decision found a fit; every other plan name stays
    viable (the CPU-path fallbacks).  ``blocks`` must be the family's
    decision result — any ``core/tiling.TilePlan`` (SeqBlocks / WkvBlocks /
    MambaBlocks through their common ``batch_tile``/``time_chunk``
    accessors) or None; the isinstance assert is what keeps a new family
    from wiring a bespoke result type past the shared interface."""
    from repro.core import tiling

    assert blocks is None or isinstance(blocks, tiling.TilePlan), blocks

    def viable(plan_name: str) -> bool:
        return blocks is not None or plan_name not in gated_plan_names

    return viable


def rwkv_viability(seq_len: int, dk: int, dv: int, *, chunk: int = 32,
                   dtype_bytes: int = 4, vmem_budget: int | None = None,
                   train: bool = False,
                   scan_plan_names: tuple[str, ...] = ("chunked_scan",)
                   ) -> Callable[[str], bool]:
    """Fig 7 ``viable=`` predicate for the rwkv6 family, from the
    kernels/wkv6 working-set model: the Pallas plan is only a real plan
    while ``choose_blocks`` finds a chunk whose (C, C, dk) intra-chunk
    tensor plus tiles fit the budget — ``train=True`` sizes the
    reverse-sweep backward instead (~3x), exactly like the lstm family's
    ``plan_viability(train=True)``.  All other plan names stay viable
    (stepwise/chunked_xla are the CPU-path fallbacks)."""
    from repro.kernels import wkv6 as wkv6_lib

    blocks = wkv6_lib.choose_blocks(
        1, seq_len, dk, dv, target=chunk, dtype_bytes=dtype_bytes,
        vmem_budget=vmem_budget, mode="bwd" if train else "fwd")
    return _plan_viable(blocks, scan_plan_names)


def _rwkv_profile_candidates(*, vmem_budget: int | None = None,
                             max_points: int = 4, seq_len: int = 64,
                             n_bh: int = 4, dk: int = 8, dv: int = 8,
                             target: int = 16) -> list[ProfileCandidate]:
    """Measured-profiler candidates for the rwkv6 family over the widened
    ``(bh_tile, chunk)`` surface: for each bh tile on ``choose_blocks``'s
    halving walk (coarsest first), jitted ``chunked_scan`` (kernels/wkv6)
    dispatches along the halving chunk search — target C first, then C/2,
    C/4, ... — keeping only points whose working set fits the budget.
    ``model_s`` comes from ``analysis.wkv6_stream_costs``."""
    import functools

    from repro import analysis
    from repro.core import factorization as fz, tiling
    from repro.kernels import wkv6 as wkv6_lib

    budget = fz.DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    r = jax.random.normal(ks[0], (n_bh, seq_len, dk), jnp.float32)
    k = jax.random.normal(ks[1], (n_bh, seq_len, dk), jnp.float32)
    v = jax.random.normal(ks[2], (n_bh, seq_len, dv), jnp.float32)
    logw = -jnp.exp(jax.random.normal(ks[3], (n_bh, seq_len, dk)))
    u = jax.random.normal(ks[4], (n_bh, dk))
    state = jax.random.normal(ks[5], (n_bh, dk, dv)) * 0.3

    out: list[ProfileCandidate] = []
    per_tile = max(1, max_points // 2)   # spread points over both axes
    for bt in tiling.halving(n_bh):
        c = max(1, min(target, seq_len))
        taken = 0
        while len(out) < max_points and taken < per_tile:
            ws = wkv6_lib.working_set_bytes(seq_len, dk, dv, c,
                                            bh_tile=bt)
            if ws <= budget:
                fn = jax.jit(functools.partial(
                    wkv6_lib.wkv6, chunk=c, bh_tile=bt))
                costs = analysis.wkv6_stream_costs(
                    seq_len, n_bh, dk, dv, c, bh_tile=bt)
                out.append(ProfileCandidate(
                    "rwkv6", "chunked_scan", {"chunk": c, "bh_tile": bt},
                    fn, (r, k, v, logw, u, state),
                    model_s=max(costs["t_compute"], costs["t_memory"])))
                taken += 1
            if c == 1:
                break
            c //= 2
        if len(out) >= max_points:
            break
    return out


def _build_rwkv_family() -> Family:
    specs = {
        "stepwise": PlanSpec("stepwise", _rwkv_stepwise, _RWKV_EXACT),
        "chunked_xla": PlanSpec("chunked_xla", _rwkv_chunked_xla,
                                _RWKV_EXACT),
        "chunked_scan": PlanSpec("chunked_scan", _rwkv_chunked_scan,
                                 _RWKV_EXACT,
                                 fwd_dispatches=1, train_dispatches=2),
    }
    return Family(
        name="rwkv6", oracle="stepwise", plans=specs, cases=_RWKV_CASES,
        dtypes=("float32", "bfloat16"), make_inputs=_rwkv_make_inputs,
        apply=_rwkv_apply, grads=_rwkv_grads, viability=rwkv_viability,
        profile_hook=_rwkv_profile_candidates)


# ===========================================================================
# mamba family — lax.scan oracle, fused Pallas stepwise selective scan
# ===========================================================================
#: fused-vs-scan agreement band: both paths run the identical per-step
#: recurrence in f32, diffs come only from XLA fusion inside a step
MAMBA_TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
             "bfloat16": dict(rtol=2e-2, atol=2e-2)}
MAMBA_GRAD_TOL = {"float32": dict(rtol=2e-4, atol=2e-5)}

_MAMBA_EXACT = EquivalencePolicy("exact", MAMBA_TOL, MAMBA_GRAD_TOL)

#: (B, T, d_inner, d_state, chunk, block_b) — C=1, C=T, non-dividing T
#: (pad path) and a non-dividing batch tile (row-mask path) all on the
#: table, so every clamp/pad branch is part of the sweep
_MAMBA_CASES = (
    Case("c8t24", (2, 24, 8, 4, 8, 2)),                     # C | T, bm | B
    Case("c1", (2, 12, 8, 4, 1, 2), heavy_grad=False),      # C=1: per-step
    Case("cT", (1, 16, 8, 4, 16, 1)),                       # C=T: one chunk
    Case("oddT", (2, 23, 8, 4, 8, 2), heavy_grad=False),    # pad path
    Case("btail", (3, 16, 8, 4, 8, 2)),                     # bm does not | B
    Case("long", (2, 96, 16, 8, 16, 2), heavy=True),
)


def _mamba_make_inputs(case: Case, dtype: str):
    import zlib

    B, T, di, ds, chunk, block_b = case.shape
    dt_ = jnp.dtype(dtype)
    seed = zlib.crc32(case.label.encode()) % (2 ** 31)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, di), dt_)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, di)))   # f32, > 0
    b = jax.random.normal(ks[2], (B, T, ds))                     # f32
    c = jax.random.normal(ks[3], (B, T, ds))                     # f32
    a = -jnp.exp(jax.random.normal(ks[4], (di, ds)))             # f32, < 0
    h0 = jax.random.normal(ks[5], (B, di, ds)) * 0.3             # f32
    return (x, dt, b, c, a, h0), chunk, block_b


def _mamba_scan(x, dt, b, c, a, h0, *, chunk, block_b):
    """Per-step lax.scan oracle — the models/mamba recurrence verbatim
    (kernels/mamba_scan.mamba_scan_ref)."""
    from repro.kernels import mamba_scan as ms_lib

    return ms_lib.mamba_scan_ref(x, dt, b, c, a, h0)


def _mamba_fused_scan(x, dt, b, c, a, h0, *, chunk, block_b, bwd=None,
                      interpret=None):
    """kernels/mamba_scan Pallas plan: ONE dispatch forward over a
    (batch-tile, time-chunk) grid with the f32 state carried in VMEM
    scratch, one reverse-sweep dispatch backward, any T and B via the
    identity zero-pad (dt=0 rows neither decay nor inject)."""
    from repro.kernels import mamba_scan as ms_lib

    if bwd is None:
        bwd = ms_lib.FUSED_BWD
    return ms_lib.mamba_scan(x, dt, b, c, a, h0, chunk=chunk,
                             block_b=block_b, bwd=bwd, interpret=interpret)


MAMBA_PLANS: dict[str, Callable] = {
    "scan": _mamba_scan,
    "fused_scan": _mamba_fused_scan,
}


def _mamba_apply(plan: str, inputs):
    args, chunk, block_b = inputs
    return MAMBA_PLANS[plan](*args, chunk=chunk, block_b=block_b)


def _mamba_grads(plan: str, inputs):
    (x, dt, b, c, a, h0), chunk, block_b = inputs

    def loss(x, dt, b, c, a, h0):
        y, h = MAMBA_PLANS[plan](x, dt, b, c, a, h0, chunk=chunk,
                                 block_b=block_b)
        return (jnp.sum(jnp.tanh(y.astype(jnp.float32)))
                + 0.5 * jnp.sum(h * h))

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(
        x, dt, b, c, a, h0)


def mamba_viability(batch: int, seq_len: int, d_inner: int, d_state: int,
                    *, dtype_bytes: int = 4,
                    vmem_budget: int | None = None, train: bool = False,
                    scan_plan_names: tuple[str, ...] = ("fused_scan",)
                    ) -> Callable[[str], bool]:
    """Fig 7 ``viable=`` predicate for the mamba family, from the
    kernels/mamba_scan working-set model: the Pallas plan is only a real
    plan while ``choose_blocks`` finds a (batch-tile, time-chunk) pair
    that fits the budget — ``train=True`` sizes the reverse-sweep
    backward instead (~3x), exactly like ``rwkv_viability(train=True)``.
    The ``scan`` oracle stays viable (it is the CPU-path fallback)."""
    from repro.kernels import mamba_scan as ms_lib

    blocks = ms_lib.choose_blocks(
        batch, seq_len, d_inner, d_state, dtype_bytes=dtype_bytes,
        vmem_budget=vmem_budget, mode="bwd" if train else "fwd")
    return _plan_viable(blocks, scan_plan_names)


def _mamba_profile_candidates(*, vmem_budget: int | None = None,
                              max_points: int = 4, batch: int = 4,
                              seq_len: int = 64, d_inner: int = 16,
                              d_state: int = 8) -> list[ProfileCandidate]:
    """Measured-profiler candidates for the mamba family over the
    substrate's (block_b, time_chunk) surface: for each batch tile on the
    halving walk (coarsest first), whole-T residency first then halving
    time chunks — the exact coarseness order ``choose_blocks`` searches.
    ``model_s`` comes from ``analysis.mamba_scan_stream_costs``."""
    import functools

    from repro import analysis
    from repro.core import factorization as fz, tiling
    from repro.kernels import mamba_scan as ms_lib

    budget = fz.DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (batch, seq_len, d_inner), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(
        ks[1], (batch, seq_len, d_inner)))
    b = jax.random.normal(ks[2], (batch, seq_len, d_state))
    c = jax.random.normal(ks[3], (batch, seq_len, d_state))
    a = -jnp.exp(jax.random.normal(ks[4], (d_inner, d_state)))
    h0 = jax.random.normal(ks[5], (batch, d_inner, d_state)) * 0.3

    out: list[ProfileCandidate] = []
    per_tile = max(1, max_points // 2)   # spread points over both axes
    for bm in tiling.halving(batch):
        taken = 0
        cn = seq_len
        while len(out) < max_points and taken < per_tile:
            ws = ms_lib.working_set_bytes(seq_len, d_inner, d_state,
                                          bm, cn)
            if ws <= budget:
                fn = jax.jit(functools.partial(
                    ms_lib.mamba_scan, chunk=cn, block_b=bm))
                costs = analysis.mamba_scan_stream_costs(
                    seq_len, batch, d_inner, d_state, bm, cn)
                out.append(ProfileCandidate(
                    "mamba", "fused_scan",
                    {"block_b": bm, "chunk": cn},
                    fn, (x, dt, b, c, a, h0),
                    model_s=max(costs["t_compute"], costs["t_memory"])))
                taken += 1
            if cn == 1:
                break
            cn //= 2
        if len(out) >= max_points:
            break
    return out


def _build_mamba_family() -> Family:
    specs = {
        "scan": PlanSpec("scan", _mamba_scan, _MAMBA_EXACT),
        "fused_scan": PlanSpec("fused_scan", _mamba_fused_scan,
                               _MAMBA_EXACT,
                               fwd_dispatches=1, train_dispatches=2),
    }
    return Family(
        name="mamba", oracle="scan", plans=specs, cases=_MAMBA_CASES,
        dtypes=("float32", "bfloat16"), make_inputs=_mamba_make_inputs,
        apply=_mamba_apply, grads=_mamba_grads, viability=mamba_viability,
        profile_hook=_mamba_profile_candidates)


register_family(_build_lstm_family())
register_family(_build_rwkv_family())
register_family(_build_mamba_family())
