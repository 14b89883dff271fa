"""The rwkv6 wkv state is stored packed, ``p`` heads to a 128-lane row
(models/rwkv.py, "Packed wkv state").

The layout comes from the shapes alone; packing round-trips exactly; the
packed ``wkv_step`` is the unpacked recurrence element for element; the
decode kernel (kernels/wkv_decode.py) steps one layer of the stacked pool
in place to ``wkv_step``'s numbers; and every cache the model builds
holds the packed leaf.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.wkv_decode import wkv_decode
from repro.models import registry, rwkv, transformer
from repro.obs import trace as trace_lib
from repro.partitioning import split

#: (heads, head size, heads per row): rwkv6-3b at full width, the reduced
#: test config, and two shapes that keep the plain layout (a head as wide
#: as the row; a head count the row's 4 heads do not divide)
SHAPES = [(40, 64, 2), (8, 32, 4), (4, 128, 1), (6, 32, 1)]
IDS = ["full-40x64", "reduced-8x32", "wide-4x128", "odd-6x32"]


def _cfg(heads, dh):
    base = get_arch("rwkv6-3b")
    return dataclasses.replace(base, d_model=heads * dh, ssm=dataclasses.replace(
        base.ssm, head_dim=dh))


def _inputs(heads, dh, batch, layers=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(kk, (batch, heads, dh)) for kk in ks[:3])
    logw = -jnp.exp(jax.random.normal(ks[3], (batch, heads, dh)))
    u = jax.random.normal(ks[4], (heads, dh))
    state = jax.random.normal(ks[5], (layers, batch, heads, dh, dh))
    return r, k, v, logw, u, state


@pytest.mark.parametrize("heads,dh,p", SHAPES, ids=IDS)
def test_heads_per_row_comes_from_the_shapes(heads, dh, p):
    assert rwkv.heads_per_row(_cfg(heads, dh)) == p


@pytest.mark.parametrize("p", [1, 2, 4])
def test_pack_round_trips_and_lays_heads_side_by_side(p):
    heads, dh = 8, 128 // p
    s = jax.random.normal(jax.random.PRNGKey(p), (3, heads, dh, dh))
    packed = rwkv.pack_state(s, p)
    assert packed.shape == (3, heads // p, dh, p * dh)
    np.testing.assert_array_equal(rwkv.unpack_state(packed, p), s)
    # lane l of packed row j holds head p*j + l // dv, column l % dv
    j, lane = heads // p - 1, np.arange(p * dh)
    np.testing.assert_array_equal(
        np.asarray(packed)[1, j, 5],
        np.asarray(s)[1, p * j + lane // dh, 5, lane % dh])


@pytest.mark.parametrize("heads,dh,p", SHAPES, ids=IDS)
def test_packed_wkv_step_is_the_unpacked_recurrence(heads, dh, p):
    r, k, v, logw, u, state = _inputs(heads, dh, batch=2)
    state = state[0]
    with jax.disable_jit():
        out, new = rwkv.wkv_step(r, k, v, logw, u, rwkv.pack_state(state, p))
        # the formulation the packed step replaced, on (B, H, dk, dv)
        kv = jnp.einsum("bhk,bhv->bhkv", k, v)
        want_out = jnp.einsum("bhk,bhkv->bhv", r, state + u[..., None] * kv)
        want = jnp.exp(logw)[..., None] * state + kv
    np.testing.assert_array_equal(rwkv.unpack_state(new, p), want)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads,dh,p", SHAPES, ids=IDS)
def test_decode_kernel_steps_one_layer_in_place(heads, dh, p):
    """The kernel's new state is ``wkv_step``'s to an f32 rounding of the
    last multiply-add (the interpreter's compiled loop may contract it);
    inactive lanes and every other layer come back bit for bit."""
    r, k, v, logw, u, state = _inputs(heads, dh, batch=2, layers=2)
    pool = rwkv.pack_state(state, p)
    active = jnp.asarray([True, False])
    out, new = wkv_decode(pool, jnp.int32(1), active, r, k, logw, v, u)
    want_out, want = rwkv.wkv_step(r, k, v, logw, u, pool[1])
    new, pool, want = np.asarray(new), np.asarray(pool), np.asarray(want)
    assert new.shape == pool.shape
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, 1], pool[1, 1])
    np.testing.assert_allclose(new[1, 0], want[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out[0], want_out[0], rtol=1e-5, atol=1e-5)


def test_every_cache_holds_the_packed_leaf():
    cfg = get_arch("rwkv6-3b")
    cache, axes = transformer.abstract_cache(cfg, 64, 16)
    assert cache["slots"][0]["wkv"].shape == (32, 64, 20, 64, 128)
    assert axes["slots"][0]["wkv"] == ("layers", "batch", "heads", None,
                                       None)
    small = get_arch("rwkv6-3b").reduced()
    model = registry.build(small)
    leaf = split(model.init_cache(3, 16))[0]["slots"][0]["wkv"]
    assert leaf.shape == (small.n_layers, 3, 2, 32, 128)
    dummy = transformer._dummy_cache_slot(small, 0, 3)["wkv"]
    assert dummy.shape == leaf.shape[1:]


def test_decode_records_the_layout():
    cfg = get_arch("rwkv6-3b").reduced()
    model = registry.build(cfg)
    params = jax.jit(lambda key: split(model.init(key))[0])(
        jax.random.PRNGKey(0))
    cache, _ = split(model.init_cache(2, 16))
    sink = trace_lib.ListSink()
    old = trace_lib.get_tracer()
    trace_lib.configure(sink=sink)
    try:
        jax.jit(model.decode_step).lower(
            params, cache, {"tokens": jnp.zeros((2,), jnp.int32)})
    finally:
        trace_lib.set_tracer(old)
    [rec] = [r for r in sink.records if r["name"] == "plan/state_layout"]
    leaf = cache["slots"][0]["wkv"]
    assert rec["attrs"] == {"family": "rwkv6", "heads_per_row": 4,
                            "shape": list(leaf.shape),
                            "bytes": leaf.size * 4}
