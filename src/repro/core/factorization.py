"""Work-unit factorization cost model (paper §3.1-3.2, Figs 2-3).

MobiRNN's central observation: the latency of a decomposed computation is

    T(n_units) = ceil(n_units / cores) * (dispatch_overhead + unit_compute)

and on a constrained accelerator (few cores, shared memory, high per-unit
overhead) the fine-grained desktop factorization (one work unit per weight
column) is dominated by the overhead term.  The same curve governs TPU
kernels: a Pallas grid with tiny blocks pays per-grid-step pipeline overhead
and underutilises the 128x128 MXU, so ``choose_block`` picks the COARSEST
block whose working set fits VMEM — the direct analogue of Fig 2c.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    name: str
    cores: int                    # parallel work-unit slots
    enqueue_overhead_s: float     # PER-WORK-UNIT driver/scheduling cost
    flops_per_core: float         # sustained FLOP/s per slot
    fast_mem_bytes: int           # shared/VMEM working-set budget
    mem_bw: float                 # bytes/s to backing memory (shared)


# Calibrated so the model reproduces the paper's measured RATIOS on the
# Nexus-5-class device (Fig 3: fine-grained GPU ~4x slower than 1-thread
# CPU; Fig 4: packed GPU ~3.9x faster; Fig 6: 4-thread CPU >= 70% of GPU)
# while keeping physically plausible magnitudes (Adreno 330 ~ 130 GFLOPs
# peak but tiny shared memory and ~us-scale per-unit dispatch; Krait CPU
# ~2 GFLOPs/core sustained on this workload).
DESKTOP_GPU = DeviceProfile("desktop-gpu", 2048, 5e-9, 5e9, 96 * 1024, 300e9)
MOBILE_GPU = DeviceProfile("mobile-gpu", 128, 5e-7, 2.2e9, 8 * 1024, 12.8e9)
MOBILE_CPU4 = DeviceProfile("mobile-cpu-4t", 4, 1e-7, 0.55e9, 1 << 20,
                            12.8e9)
# single-thread CPU baseline is the paper's plain-Java loop (~0.6 GFLOP/s
# sustained on Krait for this access pattern)
MOBILE_CPU1 = DeviceProfile("mobile-cpu-1t", 1, 5e-8, 0.6e9, 1 << 20,
                            12.8e9)
TPU_V5E = DeviceProfile("tpu-v5e", 1, 1e-6, 197e12, 128 << 20, 819e9)


def unit_time(dev: DeviceProfile, n_units: int, flops_per_unit: float,
              bytes_per_unit: float = 0.0) -> float:
    """Latency of n_units work units under the paper's scheduling model:
    every unit pays an enqueue cost (serialised through the driver — this is
    what buries the fine factorization, §3.1), then units execute in waves
    of `cores`, each wave bounded by compute or its share of memory bw."""
    waves = math.ceil(n_units / dev.cores)
    compute = flops_per_unit / dev.flops_per_core
    per_core_bw = dev.mem_bw / min(n_units, dev.cores)
    mem = bytes_per_unit / per_core_bw
    return n_units * dev.enqueue_overhead_s + waves * max(compute, mem)


def factorize_gate(dev: DeviceProfile, in_dim: int, out_dim: int,
                   cols_per_unit: int, bytes_per_elem: int = 4) -> float:
    """Latency of one gate matvec (in_dim -> out_dim) split into column
    blocks of ``cols_per_unit`` (Fig 2b: cols_per_unit=1; Fig 2c: packed)."""
    n_units = math.ceil(out_dim / cols_per_unit)
    flops = 2.0 * in_dim * cols_per_unit
    byts = bytes_per_elem * (in_dim * cols_per_unit + in_dim + cols_per_unit)
    return unit_time(dev, n_units, flops, byts)


def best_cols_per_unit(dev: DeviceProfile, in_dim: int, out_dim: int,
                       bytes_per_elem: int = 4) -> int:
    """Coarsest column block whose working set fits the fast memory —
    MobiRNN's packing rule."""
    best, best_t = 1, float("inf")
    c = 1
    while c <= out_dim:
        ws = bytes_per_elem * (in_dim * c + in_dim + c)
        if ws <= dev.fast_mem_bytes:
            t = factorize_gate(dev, in_dim, out_dim, c, bytes_per_elem)
            if t < best_t:
                best, best_t = c, t
        c *= 2
    return best


# ---------------------------------------------------------------------------
# Pallas BlockSpec chooser — the TPU instantiation of the same rule.
# ---------------------------------------------------------------------------
MXU_ALIGN = 128
#: Scoped-VMEM limit every Pallas kernel is compiled with
#: (kernels.compiler_params); below the 128 MiB of a v5e core.  Without it
#: Mosaic applies its much smaller default scoped limit.
VMEM_LIMIT_BYTES = 96 << 20
#: Buffers the Pallas pipeline holds per BlockSpec'd operand: it fetches
#: grid step i+1's block while step i computes, so every blocked input and
#: output (the resident weight stack included) sits in VMEM twice, while
#: the working-set tables count each block once.
PIPELINE_BUFFERS = 2
#: Working-set budget of the tiling choosers, keyed to the compile limit:
#: the share of it one copy of every block may take.
DEFAULT_VMEM_BUDGET = VMEM_LIMIT_BYTES // PIPELINE_BUFFERS
#: Mobile-class fast-memory budget — the constrained tier MobiRNN targets.
#: Small enough that the seed config's whole-T-resident fused-LSTM working
#: set (lane-padded terms) falls off it by T=256 (fwd) and does not fit it
#: at any T in bwd, so it is the shared stress budget for the
#: time-streaming pipeline: benchmarks/run.py (STREAM_BUDGET rows +
#: --stream-smoke / --quant-smoke, the CI invocations) and the
#: acceptance tests (test_plan_equivalence,
#: test_scheduler_state) all reference THIS constant so they assert one
#: viability surface.  Against it the int8-weight plan (fused_seq_q8,
#: Q8_WEIGHT_BYTES per weight instead of 4) keeps whole-T residency deeper
#: into T and lowers the (bm=1, tc=1) viability floors — the widened
#: decision table kernels/lstm_seq.choose_batch_block(quantized=True)
#: searches and the quant_* benchmark rows record.
MOBILE_VMEM_BUDGET = 320 << 10
#: Bytes per weight of the int8-quantized fused-LSTM plan (per-output-
#: channel symmetric int8, kernels/ref.quantize_q8) — the 4x lever on the
#: budget table's dominant (L, P+H, 4H) weight term.
Q8_WEIGHT_BYTES = 1


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def choose_block(m: int, n: int, k: int, bytes_per_elem: int = 2,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET,
                 align: int = MXU_ALIGN) -> tuple[int, int, int]:
    """Pick (bm, bn, bk) for an (m,k)x(k,n) matmul kernel: MXU-aligned,
    as coarse as fits `vmem_budget` for (A-block + B-block + out-block).

    Mirrors MobiRNN Fig 2c: prefer FEW LARGE grid steps over many small ones;
    shrink the grid only when the working set no longer fits fast memory.

    The sequence-resident LSTM kernels extend this rule along a second
    axis: kernels/lstm_seq.choose_batch_block seeds its batch tile from
    this function's ``bm`` and then searches the joint ``(block_b,
    time_chunk)`` surface — whole-T VMEM residency first, double-buffered
    time streaming second, smaller batch tiles last — so coarseness is
    preserved in the same priority order.
    """
    bm = min(round_up(m, align), 512)
    bn = min(round_up(n, align), 512)
    bk = min(round_up(k, align), 2048)

    def ws(bm, bn, bk):
        return bytes_per_elem * (bm * bk + bk * bn) + 4 * bm * bn

    # shrink the largest dim first until the working set fits
    while ws(bm, bn, bk) > vmem_budget:
        if bk >= max(bm, bn) and bk > align:
            bk //= 2
        elif bn >= bm and bn > align:
            bn //= 2
        elif bm > align:
            bm //= 2
        else:
            break
    return bm, bn, bk


def grid_steps(m: int, n: int, k: int, block: tuple[int, int, int]) -> int:
    bm, bn, bk = block
    return math.ceil(m / bm) * math.ceil(n / bn) * math.ceil(k / bk)
