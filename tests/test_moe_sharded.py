"""Sharded (shard_map) MoE dispatch must match the dense dispatch bit-for-bit
on a real multi-device mesh — forward and gradients."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.models import ModelConfig, init_lm, forward
from repro.models.lm import lm_loss
from repro.dist.context import mesh_context

cfg_d = ModelConfig(name="moe", arch_type="moe", n_layers=2, d_model=64, n_heads=4,
                    n_kv=4, d_ff=128, vocab=64, n_experts=4, top_k=2, n_shared=1,
                    d_expert=64, capacity_factor=8.0, moe_dispatch="dense")
cfg_s = cfg_d.with_(moe_dispatch="sharded")
key = jax.random.PRNGKey(0)
params = init_lm(key, cfg_d)
toks = jax.random.randint(key, (4, 16), 0, 64)
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((4, 2), ("data", "model"))
ref, _ = forward(params, cfg_d, {"tokens": toks})
with mesh, mesh_context(mesh):
    out, _ = jax.jit(lambda p, t: forward(p, cfg_s, {"tokens": t}))(params, toks)
assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
g_ref = jax.grad(lambda p: lm_loss(p, cfg_d, {"tokens": toks, "labels": toks}))(params)
with mesh, mesh_context(mesh):
    g_s = jax.jit(jax.grad(lambda p: lm_loss(p, cfg_s, {"tokens": toks, "labels": toks})))(params)
errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_s))]
assert max(errs) < 5e-4, max(errs)
print("SHARDED_MOE_MATCH")
"""


def test_sharded_moe_matches_dense_on_mesh():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_MOE_MATCH" in r.stdout
