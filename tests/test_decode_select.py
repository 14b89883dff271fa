"""The decode tick's active-lane select runs per layer inside the layer
scan (models/transformer.decode_step), not as a pass over the stacked
state pool after it.

The equivalence test holds the tick to the formulation it replaced —
the unmasked ``decode_step`` followed by one ``where`` over every stacked
slot leaf — bit for bit, per serving family.  The structural guard reads
the engine's ``decode/base`` plan as a jaxpr and finds no select over a
stacked (n_groups, B, ...) slot leaf outside the scan.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro import steps as steps_lib
from repro.configs import get_arch
from repro.models import registry, transformer
from repro.partitioning import split
from repro.serving import EngineConfig, SlotEngine

ACTIVE = np.array([True, False, True, False])
POS = np.array([3, 5, 2, 7], np.int32)
#: compiled agreement of the two formulations: a few f32 ulps of values of
#: order one (the state and logits of these tiny models)
F32_FUSION_RTOL, F32_FUSION_ATOL = 1e-5, 1e-5


def _tiny(arch):
    cfg = get_arch(arch).reduced()
    if arch == "qwen2-0.5b":
        cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, n_heads=2,
                                  n_kv_heads=1, head_dim=16, d_ff=128,
                                  vocab=128)
    return cfg


def _params(model):
    return jax.jit(lambda k: split(model.init(k))[0])(jax.random.PRNGKey(0))


def _filled_cache(model, n, max_seq, seed):
    """A pool cache whose every float leaf holds seeded noise, so a lane
    that kept its state and a lane that was advanced cannot agree by
    accident.  Both formulations step this one cache, rwkv6's packed wkv
    leaf included, as ``model.init_cache`` lays it out."""
    cache, _ = split(model.init_cache(n, max_seq))
    leaves, tree = jax.tree.flatten(cache["slots"])
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    leaves = [jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
              if jnp.issubdtype(a.dtype, jnp.floating) else a
              for k, a in zip(keys, leaves)]
    return {"pos": jnp.asarray(POS), "slots": jax.tree.unflatten(tree, leaves)}


def _whole_stack_select(cfg, params, cache, batch):
    """The tick before the select moved into the scan: the unmasked step,
    then one ``where`` over each stacked (n_groups, B, ...) slot leaf."""
    active = batch["active"]
    logits, new = transformer.decode_step(params, cfg, cache,
                                          {"tokens": batch["tokens"]})

    def sel(n, o):
        return jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o)

    return logits, {"pos": jnp.where(active, new["pos"], cache["pos"]),
                    "slots": jax.tree.map(sel, new["slots"], cache["slots"])}


def _tick(cfg, params, cache, batch):
    """(logits, cache) of the old formulation and of the engine's step."""
    ref_logits, ref = _whole_stack_select(cfg, params, cache, batch)
    logits, ok, got = steps_lib.guarded_decode_step(cfg, params, cache,
                                                    batch)
    return (ref_logits, ref), (logits, ok, got)


def _assert_same(ref_logits, ref, logits, ok, got, cache, *, exact):
    """Active logits and every cache leaf against the old formulation
    (bit for bit where ``exact``); inactive lanes against the input cache,
    always bit for bit."""
    same = (np.testing.assert_array_equal if exact else functools.partial(
        np.testing.assert_allclose, rtol=F32_FUSION_RTOL,
        atol=F32_FUSION_ATOL))
    assert np.asarray(ok).all()
    same(np.asarray(logits)[ACTIVE], np.asarray(ref_logits)[ACTIVE])
    np.testing.assert_array_equal(np.asarray(got["pos"]),
                                  np.asarray(ref["pos"]))
    np.testing.assert_array_equal(np.asarray(got["pos"])[~ACTIVE],
                                  POS[~ACTIVE])
    want = jax.tree_util.tree_leaves_with_path(ref["slots"])
    have = jax.tree.leaves(got["slots"])
    old = jax.tree.leaves(cache["slots"])
    assert len(want) == len(have) == len(old) > 0
    moved = []
    for (path, w), h, o in zip(want, have, old):
        name = jax.tree_util.keystr(path)
        h, w, o = np.asarray(h), np.asarray(w), np.asarray(o)
        same(h, w, err_msg=name)
        np.testing.assert_array_equal(h[:, ~ACTIVE], o[:, ~ACTIVE],
                                      err_msg=name)
        moved.append(not np.array_equal(h[:, ACTIVE], o[:, ACTIVE]))
    # the active lanes' state moved, or the checks above would hold for a
    # step that changed nothing
    assert any(moved)


@pytest.mark.parametrize("arch", ["rwkv6-3b",               # rwkv
                                  "jamba-1.5-large-398b",   # mamba + attn
                                  "qwen2-0.5b"])            # attention
def test_in_scan_select_matches_whole_stack_select(arch):
    cfg = _tiny(arch)
    model = registry.build(cfg)
    params = _params(model)
    cache = _filled_cache(model, len(ACTIVE), 16, seed=1)
    toks = jax.random.randint(jax.random.PRNGKey(2), (len(ACTIVE),), 0,
                              cfg.vocab, jnp.int32)
    batch = {"tokens": toks, "active": jnp.asarray(ACTIVE)}

    # op by op, the two formulations apply the same operations to the same
    # values: bit for bit
    with jax.disable_jit():
        (ref_logits, ref), (logits, ok, got) = _tick(cfg, params, cache, batch)
    _assert_same(ref_logits, ref, logits, ok, got, cache, exact=True)

    # compiled, as the engine runs it: XLA's CPU backend fuses the state
    # update with the select, which may contract a multiply-add or inline
    # an exp differently, so active lanes agree to f32 rounding; inactive
    # lanes still come back exactly as they went in
    (ref_logits, ref), (logits, ok, got) = jax.jit(
        lambda p, c, b: _tick(cfg, p, c, b))(params, cache, batch)
    _assert_same(ref_logits, ref, logits, ok, got, cache, exact=False)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(j, ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, Jaxpr):
                yield j


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _selects_outside_scan(jaxpr):
    """Output shapes of every ``select_n`` not inside a ``scan``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            continue
        if eqn.primitive.name == "select_n":
            yield eqn.outvars[0].aval.shape
        for sub in _sub_jaxprs(eqn):
            yield from _selects_outside_scan(sub)


def test_no_pool_wide_select_outside_the_layer_scan():
    cfg = _tiny("rwkv6-3b")
    model = registry.build(cfg)
    params = _params(model)
    engine = SlotEngine(model, params, config=EngineConfig(n_slots=4,
                                                           max_seq=16))
    plan = engine.scheduler.plans["decode/base"].fn
    cache = _filled_cache(model, len(ACTIVE), 16, seed=1)
    batch = {"tokens": jnp.zeros((len(ACTIVE),), jnp.int32),
             "active": jnp.asarray(ACTIVE),
             "poison": jnp.zeros((len(ACTIVE),), bool)}
    stacked = {a.shape for a in jax.tree.leaves(cache["slots"])}
    assert all(s[1] == len(ACTIVE) for s in stacked)

    jaxpr = jax.make_jaxpr(plan)(params, cache, batch).jaxpr
    assert any(e.primitive.name == "scan" for e in _walk(jaxpr))
    outside = set(_selects_outside_scan(jaxpr))
    assert not outside & stacked, sorted(outside & stacked)

