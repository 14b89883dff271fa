"""Family-generic tiling substrate: one (batch_tile, time_chunk) layer.

MobiRNN's tuning loop — pick the COARSEST work unit whose working set fits
fast memory, stream what does not fit, shrink the work unit only as a last
resort — is a property of the recurrence SHAPE, not of any one family.
This module owns the three pieces every registered family shares, so the
LSTM, RWKV6 and Mamba budget tables are one code path, not three:

* the **working-set-term algebra**: a named-term accumulator
  (``WorkingSet``) plus the residency helpers every term table is built
  from — ``weight_dtype_bytes`` (the ``quantized=`` / ``w_dtype_bytes=``
  parameterisation), ``streamed_rows`` (whole-axis residency vs
  ``STREAM_SLOTS`` double-buffered chunk windows), ``bwd_window_rows``
  (the one-row trajectory overlap of reverse sweeps) and
  ``streamed_axis_rows`` (total rows a streamed axis actually moves,
  clamped/padded tail re-reads included — the HBM-traffic side of the
  same decision, used by the ``analysis`` stream-cost rooflines);
* the **fwd/bwd mode split**: ``check_mode`` validates the two-phase
  contract — ``mode="bwd"`` sizes the reverse-sweep dispatch, which
  strictly dominates the trajectory-emitting forward that feeds it
  (~3x at the paper shapes), so one number gates both training
  dispatches;
* the **coarseness-ordered joint search** (``joint_search``): whole-axis
  residency at the coarsest batch tile first, then streamed time chunks
  from coarse to fine, then smaller batch tiles — the exact priority
  order of kernels/lstm_seq.choose_batch_block, now family-generic.
  ``kernels/lstm_seq.choose_batch_block`` (-> ``lstm.plan_viability``),
  ``kernels/wkv6.choose_blocks`` (-> ``plans.rwkv_viability``) and
  ``kernels/mamba_scan.choose_blocks`` (-> ``plans.mamba_viability``)
  are all thin ``fits`` closures over this one search.

ROADMAP §Tiling substrate holds the terms-x-family decision table.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Protocol, runtime_checkable

#: Streamed axes are double-buffered: one window computes while the next
#: prefetches (kernels/lstm_seq._x_chunk_dma and the wkv6/mamba analogues).
STREAM_SLOTS = 2
#: TPU vreg lane count.  VMEM holds an array's minor axis in whole
#: 128-lane tiles, and a manual DMA window must span whole tiles, so a
#: working-set term costs its minor axis padded to ``lanes(width)``.
LANES = 128


@runtime_checkable
class TilePlan(Protocol):
    """The ONE interface every family's tiling result presents.

    ``joint_search`` returns a raw ``(batch_tile, time_chunk)`` pair; each
    family wraps it in its own NamedTuple with family-flavoured field names
    (``SeqBlocks.block_b``, ``WkvBlocks.bh_tile``, ``MambaBlocks.block_b``).
    Family-generic consumers — the ``plans.py`` viability factories, the
    analysis rooflines, anything that only needs "how coarse is the batch
    axis, how is time streamed" — go through these two accessors instead
    of the per-family spellings:

    * ``batch_tile`` — rows of the batch-like axis per grid step (batch
      for LSTM/Mamba, fused B*H heads for WKV6);
    * ``time_chunk`` — streamed time-window length, or None for whole-axis
      residency (the LSTM no-streaming fast path; the always-chunked
      wkv6/mamba grids never return None).
    """

    @property
    def batch_tile(self) -> int: ...

    @property
    def time_chunk(self) -> int | None: ...


def check_mode(mode: str) -> str:
    """Validate the fwd/bwd phase split shared by every family's table."""
    if mode not in ("fwd", "bwd"):
        raise ValueError(f"mode must be 'fwd' or 'bwd', got {mode!r}")
    return mode


def weight_dtype_bytes(dtype_bytes: int, w_dtype_bytes: int | None = None,
                       quantized: bool = False) -> int:
    """Bytes per weight under the shared parameterisation: explicit
    ``w_dtype_bytes`` wins; otherwise quantized plans hold int8 weights
    (1 byte) and float plans hold activation-width weights."""
    if w_dtype_bytes is not None:
        return w_dtype_bytes
    return 1 if quantized else dtype_bytes


def streamed_rows(seq_len: int, time_chunk: int | None,
                  slots: int = STREAM_SLOTS) -> int:
    """VMEM rows a (possibly streamed) sequence-axis buffer holds:
    the whole axis when ``time_chunk`` is None, else ``slots``
    double-buffered windows of ``min(time_chunk, seq_len)`` rows."""
    if time_chunk is None:
        return seq_len
    return slots * min(time_chunk, seq_len)


def bwd_window_rows(seq_len: int, time_chunk: int) -> int:
    """Rows per reverse-sweep trajectory window: chunked backward passes
    need the t-1 row of the previous chunk, so each window carries one
    overlap row whenever more than one chunk exists."""
    tc = min(time_chunk, seq_len)
    return tc + 1 if seq_len > tc else tc


def ceil_chunks(seq_len: int, time_chunk: int) -> int:
    """Grid extent of a streamed sequence axis: ceil(T / tc)."""
    tc = max(1, min(time_chunk, seq_len))
    return -(-seq_len // tc)


def streamed_axis_rows(seq_len: int, time_chunk: int | None) -> int:
    """TOTAL rows a streamed axis moves across HBM — the traffic-side twin
    of ``streamed_rows``: every chunk window is a full ``tc`` rows, so a
    non-dividing tail re-reads (clamped windows, lstm_seq) or re-moves
    (identity zero-padding, wkv6/mamba) up to ``tc - 1`` rows; pricing
    ``nc * tc`` keeps the analysis rooflines honest about that."""
    if time_chunk is None:
        return seq_len
    tc = max(1, min(time_chunk, seq_len))
    return ceil_chunks(seq_len, tc) * tc


def pad_tiles(n: int, tile: int) -> int:
    """Length of an axis zero-padded up to the tile grid (manual-DMA
    kernels address tiles themselves, so the grid must divide exactly)."""
    return ceil_chunks(n, tile) * tile


def lanes(width: int) -> int:
    """A minor axis of ``width`` as VMEM holds it: whole ``LANES`` tiles."""
    return pad_tiles(width, LANES)


@dataclasses.dataclass
class WorkingSet:
    """Named-term working set of ONE grid step — the algebra the budget
    tables are written in.  Families ``add`` each resident block under a
    stable name (``weights``, ``x_block``, ``traj``, ...); ``bwd_only``
    terms participate only under ``mode="bwd"`` — the shared encoding of
    the ~3x fwd/bwd split.  ``total()`` is what the budget compares;
    ``terms`` is what the ROADMAP decision table and tests introspect."""
    mode: str = "fwd"
    terms: dict[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        check_mode(self.mode)

    def add(self, name: str, nbytes: int, bwd_only: bool = False
            ) -> "WorkingSet":
        if bwd_only and self.mode != "bwd":
            return self
        self.terms[name] = self.terms.get(name, 0) + int(nbytes)
        return self

    def total(self) -> int:
        return sum(self.terms.values())


def halving(start: int, floor: int = 1) -> Iterator[int]:
    """Coarse-to-fine halving walk: start, start//2, ..., floor."""
    c = max(floor, start)
    while True:
        yield c
        if c <= floor:
            return
        c = max(c // 2, floor)


def joint_search(batch: int, seq_len: int,
                 fits: Callable[[int, int | None], bool], *,
                 seed_batch_tile: int | None = None,
                 allow_chunk: bool = True,
                 whole_t_first: bool = True,
                 chunk_start: int | None = None
                 ) -> tuple[int, int | None] | None:
    """The coarseness-ordered joint ``(batch_tile, time_chunk)`` search.

    ``fits(batch_tile, time_chunk)`` is the family's working-set-vs-budget
    predicate (``time_chunk=None`` = whole-axis residency).  The priority
    order is MobiRNN's Fig 2c rule extended along the time axis:

    1. whole-T residency at the current batch tile (no streaming
       machinery at all) when ``whole_t_first`` and it fits;
    2. otherwise STREAM the time axis — a halving sweep from
       ``chunk_start`` (default ``seq_len // 2``) down to 1 takes the
       first, coarsest chunk that fits, keeping the batch tile coarse
       (full MXU rows, few grid steps) and hiding the window DMA behind
       compute instead of multiplying grid steps;
    3. only when even ``tc=1`` does not fit, halve the batch tile and
       retry — shrinking it also shrinks the weight-independent terms.

    Returns ``(batch_tile, time_chunk)`` — ``time_chunk=None`` only from
    step 1 — or None when even ``(1, 1)`` does not fit: the weight-class
    resident terms themselves blow the budget, and the caller routes to
    its fallback plan.  ``allow_chunk=False`` restores the pre-streaming
    surface (whole-axis residency or bust); ``whole_t_first=False`` serves
    families whose kernels always run chunked (the wkv6/mamba grids), for
    which "whole-T" is just the coarsest chunk candidate.
    """
    bm = batch if seed_batch_tile is None else seed_batch_tile
    bm = max(1, min(bm, batch))
    start = max(seq_len // 2, 1) if chunk_start is None else chunk_start
    while bm >= 1:
        if whole_t_first and fits(bm, None):
            return bm, None
        if allow_chunk:
            for tc in halving(start):
                if fits(bm, tc):
                    return bm, tc
        if bm == 1:
            break
        bm = max(bm // 2, 1)
    return None
