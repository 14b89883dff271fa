"""Distributed-path correctness: the shard_map expert-parallel MoE and the
sequence-parallel wkv pipeline must equal their single-device references.

These need >1 XLA device, so they run in a subprocess with
``--xla_force_host_platform_device_count=8`` (the main test process must
keep the single real device; see conftest note)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# multi-second integration sweeps: excluded from the quick loop (-m "not slow")
pytestmark = pytest.mark.slow


def run_in_devices(code: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]


MOE_EP = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import moe as moe_lib
from repro.partitioning import split, make_rules, use_rules
cfg = get_arch('olmoe-1b-7b').reduced()
mesh = jax.make_mesh((2, 4), ('data', 'model'))
rules = make_rules(mesh)
p, _ = split(moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
with mesh, use_rules(rules):
    out_ep, _ = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, cfg,
                                                       no_drop=True))(p, x)
out_d, _ = moe_lib._apply_moe_dense(p, x, cfg, True)
np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_d),
                           rtol=2e-4, atol=2e-4)
def le(p):
    with mesh, use_rules(rules):
        o, _ = moe_lib.apply_moe(p, x, cfg, no_drop=True)
    return jnp.sum(o ** 2)
def ld(p):
    o, _ = moe_lib._apply_moe_dense(p, x, cfg, True)
    return jnp.sum(o ** 2)
g1, g2 = jax.jit(jax.grad(le))(p), jax.grad(ld)(p)
for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-4, atol=3e-4)
print('ok')
"""

SEQPAR = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import rwkv
from repro.partitioning import split, make_rules, use_rules
cfg = get_arch('rwkv6-3b').reduced()
mesh = jax.make_mesh((2, 4), ('data', 'model'))
rules = make_rules(mesh)
p, _ = split(rwkv.init_tmix(jax.random.PRNGKey(0), cfg, jnp.float32))
B, S, d = 4, 32, cfg.d_model
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d)) * 0.5
xp = jax.random.normal(jax.random.PRNGKey(2), (B, d)) * 0.5
H, dh = rwkv.n_heads(cfg), cfg.ssm.head_dim
s0 = jax.random.normal(jax.random.PRNGKey(3), (B, H, dh, dh)) * 0.3
o1, sh1, st1 = rwkv._apply_tmix_local(p, cfg, x, xp, s0)
with mesh, use_rules(rules):
    o2, sh2, st2 = jax.jit(lambda p, x, xp, s0: rwkv.apply_tmix(
        p, cfg, x, xp, s0))(p, x, xp, s0)
np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=4e-4,
                           atol=4e-4)
np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), rtol=4e-4,
                           atol=4e-4)
np.testing.assert_allclose(np.asarray(sh1), np.asarray(sh2), rtol=1e-5,
                           atol=1e-5)
print('ok')
"""

FULL_MODEL_SEQPAR = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.models import registry
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.partitioning import split, make_rules, use_rules, tree_shardings
cfg = get_arch('rwkv6-3b').reduced()
m = registry.build(cfg)
params, axes = split(m.init(jax.random.PRNGKey(0)))
batch = registry.make_batch(cfg, ShapeConfig('s', 32, 4, 'train'),
                            jax.random.PRNGKey(1))
logits_1dev, _ = m.forward(params, batch)
mesh = make_host_mesh(model_axis=4)
rules = make_rules(mesh)
with mesh, use_rules(rules):
    logits_dist, _ = jax.jit(lambda p, b: m.forward(p, b))(params, batch)
np.testing.assert_allclose(np.asarray(logits_1dev, np.float32),
                           np.asarray(logits_dist, np.float32),
                           rtol=3e-3, atol=3e-3)
print('ok')
"""


@pytest.mark.parametrize("name,code", [
    ("moe_expert_parallel", MOE_EP),
    ("rwkv_seq_parallel", SEQPAR),
    ("rwkv_full_model_dist_equals_local", FULL_MODEL_SEQPAR),
])
def test_distributed(name, code):
    run_in_devices(code)
