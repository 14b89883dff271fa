"""Data pipeline, optimizer, checkpointing, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.data import SyntheticLM, har
from repro.optim import AdamW, warmup_cosine


def test_har_shapes_and_balance():
    train, test = har.make_har(n_train=600, n_test=120, seed=0)
    assert train.x.shape == (600, 128, 9)
    assert test.y.shape == (120,)
    assert set(np.unique(train.y)) <= set(range(6))
    counts = np.bincount(train.y, minlength=6)
    assert counts.min() > 0


def test_har_classes_are_separable_by_simple_stats():
    """Laying must differ from walking in gravity orientation & dynamics."""
    train, _ = har.make_har(n_train=400, n_test=10, seed=1)
    walking = train.x[train.y == 0]
    laying = train.x[train.y == 5]
    if len(walking) and len(laying):
        walk_dyn = np.abs(walking[:, :, :3]).mean()
        lay_dyn = np.abs(laying[:, :, :3]).mean()
        assert walk_dyn > 3 * lay_dyn


def test_har_batches_iterate():
    train, _ = har.make_har(n_train=100, n_test=10)
    it = har.batches(train, 16, epochs=1)
    xs, ys = next(it)
    assert xs.shape == (16, 128, 9) and ys.shape == (16,)


def test_synthetic_lm_structure():
    lm = SyntheticLM(vocab=97, seed=0)
    rng = np.random.default_rng(0)
    toks = lm.sample(rng, 4, 64)
    assert toks.shape == (4, 64)
    assert toks.min() >= 0 and toks.max() < 97
    # determinstic component: a*prev + prev2 + b appears often
    det = (lm.a * toks[:, 1:-1] + toks[:, :-2] + lm.b) % 97
    frac = (det == toks[:, 2:]).mean()
    assert frac > 0.4


def test_adamw_decreases_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, state, m = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.2
    assert int(state["step"]) == 100


def test_adamw_grad_clip():
    opt = AdamW(lr=0.0, grad_clip=1.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    _, _, m = opt.update({"w": jnp.full(3, 100.0)}, state, params)
    assert float(m["grad_norm"]) > 1.0   # reported pre-clip


def test_warmup_cosine_shape():
    fn = warmup_cosine(1.0, warmup=10, total=100)
    assert float(fn(jnp.asarray(5))) < 1.0
    assert abs(float(fn(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(fn(jnp.asarray(100))) < float(fn(jnp.asarray(50)))


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": {"b": jnp.arange(6).reshape(2, 3).astype(jnp.float32)},
            "c": jnp.ones((4,), jnp.int32)}
    ckpt.save(str(tmp_path), 7, tree)
    assert ckpt.latest_step(str(tmp_path)) == 7
    template = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    back = ckpt.restore(str(tmp_path), template)
    np.testing.assert_array_equal(back["a"]["b"], tree["a"]["b"])
    np.testing.assert_array_equal(back["c"], tree["c"])


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt.restore(str(tmp_path), {"w": jnp.zeros((3, 3))})


def test_checkpoint_picks_latest(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": jnp.zeros(1)})
    ckpt.save(str(tmp_path), 12, {"w": jnp.ones(1)})
    back = ckpt.restore(str(tmp_path), {"w": jnp.zeros(1)})
    assert float(back["w"][0]) == 1.0


# ---------------------------------------------------------------------------
def test_serving_engine_end_to_end():
    from repro.configs import get_arch
    from repro.models import registry
    from repro.partitioning import split
    from repro.serving import EngineConfig, Request, SlotEngine

    cfg = get_arch("qwen2-0.5b").reduced()
    m = registry.build(cfg)
    params, _ = split(m.init(jax.random.PRNGKey(0)))
    eng = SlotEngine(m, params, config=EngineConfig(n_slots=2, max_seq=32))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, (6,)).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    res = eng.serve(reqs)
    assert len(res) == 3
    assert all(r.tokens.shape == (4,) for r in res)
    # one resident cache for the engine's life; one scratch checkout per
    # request, each returned when its lane lands in a slot
    assert (eng.pool.stats.checkouts, eng.pool.stats.outstanding) == (1, 1)
    sp = eng._scratch_pool.stats
    assert (sp.buffers_built, sp.checkouts, sp.outstanding) == (2, 3, 0)


@pytest.mark.slow
def test_serving_engine_greedy_matches_manual_decode():
    """SlotEngine output == manual prefill+greedy loop with the raw
    model."""
    from repro.configs import get_arch
    from repro.models import registry
    from repro.partitioning import split
    from repro.serving import EngineConfig, Request, SlotEngine

    cfg = get_arch("yi-9b").reduced()
    m = registry.build(cfg)
    params, _ = split(m.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, (5,)).astype(np.int32)

    eng = SlotEngine(m, params, config=EngineConfig(n_slots=1, max_seq=16))
    out = eng.serve([Request(0, prompt, max_new_tokens=3)])[0].tokens

    cache, _ = split(m.init_cache(1, 16))
    logits, cache = m.prefill(params, cache, {"tokens": prompt[None]})
    toks = []
    tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
    for _ in range(3):
        toks.append(int(tok[0]))
        logits, cache = m.decode_step(params, cache, {"tokens": tok})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    np.testing.assert_array_equal(out, np.array(toks))
