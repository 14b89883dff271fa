"""Real-width Mosaic compiles for a described TPU v5e — no chip needed.

The TPU compiler is installed here and compiles for a topology that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes that break the (8, 128) tiling rule, DMA windows narrower than
a lane tile, unsupported in-kernel reshapes, and working sets past the
scoped-VMEM limit.  Every case compiles with ``interpret=False`` (the CPU
backend would otherwise pick the interpreter) and asserts the kernels are
in the compiled program as ``tpu_custom_call``s.

The kernels that no served model calls yet (wkv6, mamba_scan, decode_attn,
flash_prefill) are strict xfails that name the compiler's refusal: the
change that routes a family through its kernel makes its case pass, and
must then drop the mark.

The topology is described inside a module-scoped fixture — never at
import — because only one process may load the TPU library at a time and
every test worker imports every test file.
"""
from __future__ import annotations

import os
import traceback

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.mobirnn_lstm import LSTMConfig
from repro.core import lstm
from repro.kernels import lstm_seq

PAPER = LSTMConfig()                                   # 2 x 32, T=128, 9 ch
FIG56 = LSTMConfig().with_complexity(256, 3)           # Fig 5/6's largest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_v5e(one_chip):
    """``compile_v5e(fn, *shapes) -> HLO text`` of ``fn`` compiled for one
    v5e chip.  The persistent compilation cache is off meanwhile: an entry
    written for a described device cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def compile_(fn, *args):
        return jax.jit(fn).lower(*place(args)).compile().as_text()

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield compile_
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _lstm_args(cfg: LSTMConfig, batch: int):
    params = jax.eval_shape(lambda: lstm.init_params(jax.random.PRNGKey(0),
                                                     cfg))
    x = jax.ShapeDtypeStruct((batch, cfg.seq_len, cfg.input_dim),
                             jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return params, x, y


def _plan_fn(plan: str, cfg: LSTMConfig, grad: bool):
    fwd = lstm.FORWARD_PLANS[plan]

    def forward(p, x, c):
        return fwd(p, x, c, interpret=False)

    if grad:
        return jax.grad(lambda p, x, y: lstm.loss_fn(p, x, y, cfg,
                                                     forward=forward))
    return lambda p, x: forward(p, x, cfg)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("plan", ["fused_seq", "fused_seq_q8"])
def test_fused_seq_paper_width(compile_v5e, plan, batch, grad):
    params, x, y = _lstm_args(PAPER, batch)
    args = (params, x, y) if grad else (params, x)
    text = compile_v5e(_plan_fn(plan, PAPER, grad), *args)
    # the dispatch contract: 1 kernel forward, 2 under value_and_grad
    assert text.count("tpu_custom_call") == (2 if grad else 1)


@pytest.mark.parametrize("plan,batch", [("fused_seq_q8", 64),
                                        ("fused_seq", 256)])
def test_fused_seq_fig56_grad(compile_v5e, plan, batch):
    """3 x 256 backward: the tiles chosen against DEFAULT_VMEM_BUDGET must
    fit the compile limit (both overflowed scoped VMEM before the limit
    was set and the budget keyed to it)."""
    text = compile_v5e(_plan_fn(plan, FIG56, True), *_lstm_args(FIG56, batch))
    assert text.count("tpu_custom_call") == 2


def test_fused_cell_paper_width(compile_v5e):
    """H=32: the per-cell kernel's gate-major (K, 4*bh) weight block."""
    text = compile_v5e(_plan_fn("fused_cell", PAPER, False),
                       *_lstm_args(PAPER, 1)[:2])
    assert text.count("tpu_custom_call") == PAPER.n_layers


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "q8"])
def test_streamed_paper_width(compile_v5e, quantized, grad):
    """time_chunk=16 at H=32: the DMA windows of every streamed array span
    whole 128-lane tiles (lane padding)."""
    L, H, B, T = PAPER.n_layers, PAPER.hidden, 64, PAPER.seq_len
    op = lstm_seq.lstm_seq_q8 if quantized else lstm_seq.lstm_seq

    def run(w, b, x):
        c, h = op(w, b, x, block_b=B, time_chunk=16, bwd_block_b=B,
                  bwd_time_chunk=16, interpret=False)
        return jnp.sum(c) + jnp.sum(h)

    fn = jax.grad(run, argnums=(0, 1, 2)) if grad else run
    shapes = (jax.ShapeDtypeStruct((L, 2 * H, 4 * H), jnp.float32),
              jax.ShapeDtypeStruct((L, 4 * H), jnp.float32),
              jax.ShapeDtypeStruct((B, T, H), jnp.float32))
    text = compile_v5e(fn, *shapes)
    assert text.count("tpu_custom_call") == (2 if grad else 1)


# ---------------------------------------------------------------------------
# Kernels off the served path: recorded refusals (strict xfail)
# ---------------------------------------------------------------------------
class Refused(Exception):
    """The compile failed with the refusal recorded in the xfail reason."""


def _expect_refusal(compile_v5e, fn, args, fragment: str) -> None:
    try:
        text = compile_v5e(fn, *args)
    except Exception as err:
        seen = "".join(traceback.format_exception(err))
        if fragment in seen:
            raise Refused(fragment) from err
        raise
    assert "tpu_custom_call" in text


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


BF16 = jnp.bfloat16


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "wkv6 at rwkv6-3b width (40 heads x 64, T=128, chunk 32, bh_tile 1): "
    "the (1, 64) u block breaks the (8, 128) block-shape rule; at bh_tile "
    "8 the in-kernel cumsum has no Pallas TPU lowering"))
def test_wkv6_rwkv6_3b_width(compile_v5e):
    from repro.kernels import wkv6
    BH, T, dk = 40, 128, 64
    _expect_refusal(
        compile_v5e,
        lambda r, k, v, lw, u, s: wkv6.wkv6(r, k, v, lw, u, s, chunk=32,
                                            interpret=False),
        (_s((BH, T, dk), BF16), _s((BH, T, dk), BF16), _s((BH, T, dk), BF16),
         _s((BH, T, dk)), _s((BH, dk)), _s((BH, dk, dk))),
        "divisible by 8 and 128")


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "mamba_scan at jamba-1.5-large width (d_inner 16384, d_state 16, T=128, "
    "chunk 64): the lax.scan with stacked outputs in _chunk_math raises "
    "NotImplementedError in Pallas' scan lowering (_scan_lowering_rule)"))
def test_mamba_scan_jamba_width(compile_v5e):
    from repro.kernels import mamba_scan
    B, T, di, ds = 1, 128, 16384, 16
    _expect_refusal(
        compile_v5e,
        lambda x, dt, b, c, a, h: mamba_scan.mamba_scan(
            x, dt, b, c, a, h, chunk=64, interpret=False),
        (_s((B, T, di), BF16), _s((B, T, di)), _s((B, T, ds)),
         _s((B, T, ds)), _s((di, ds)), _s((B, di, ds))),
        "_scan_lowering_rule")


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "decode_attn at qwen2-0.5b width (14 q / 2 kv heads x 64, 4 lanes, "
    "1024 cache rows): the (1, 1, 64) q block breaks the (8, 128) "
    "block-shape rule"))
def test_decode_attn_qwen2_width(compile_v5e):
    from repro.kernels import decode_attn
    _expect_refusal(
        compile_v5e,
        lambda q, k, v, n: decode_attn.decode_attn(q, k, v, n,
                                                   interpret=False),
        (_s((4, 14, 64), BF16), _s((4, 1024, 2, 64), BF16),
         _s((4, 1024, 2, 64), BF16), _s((4,), jnp.int32)),
        "divisible by 8 and 128")


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "flash_prefill at qwen2-0.5b width (14 q / 2 kv heads x 64, S=1024): "
    "Mosaic infer-vector-layout refuses the (128,) -> (128, 1) mask "
    "reshape ('unsupported shape cast')"))
def test_flash_prefill_qwen2_width(compile_v5e):
    from repro.kernels import flash_prefill
    _expect_refusal(
        compile_v5e,
        lambda q, k, v: flash_prefill.flash_prefill(q, k, v,
                                                    interpret=False),
        (_s((1, 1024, 14, 64), BF16), _s((1, 1024, 2, 64), BF16),
         _s((1, 1024, 2, 64), BF16)),
        "unsupported shape cast")
