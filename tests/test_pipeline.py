"""Pipeline parallelism: PP loss == plain loss (exactness), on 2 fake pods."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np, jax.numpy as jnp
    from repro.configs.base import reduce_for_smoke
    from repro.configs.registry import get_config
    from repro.models import model
    from repro.models.modules import Policy
    from repro.launch.pipeline import make_pp_loss, stack_stage_params
    from jax import set_mesh
    from repro.launch.mesh import make_mesh
    import dataclasses

    cfg = reduce_for_smoke(get_config("stablelm-1.6b"))
    cfg = dataclasses.replace(cfg, num_layers=4)   # 2 stages x 2 periods
    pol = Policy(attn_q_chunk=32, attn_kv_chunk=32)
    params = model.init_params(cfg, jax.random.PRNGKey(0), pol)
    rng = np.random.default_rng(0)
    B, S = 4, 32
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    want, _ = model.loss_fn(params, batch, cfg, pol)

    mesh = make_mesh((2,), ("pod",))
    stacked = stack_stage_params(cfg, params, 2)
    with set_mesh(mesh):
        pp_loss = make_pp_loss(cfg, pol, mesh, microbatches=2)
        got = jax.jit(pp_loss)(stacked, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-4)
    # gradients flow through the pipeline (ppermute transpose)
    g = jax.grad(lambda p: pp_loss(p, batch))(stacked)
    gn = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    print("PP-OK", float(got), float(want))
""")


@pytest.mark.slow
def test_pp_loss_matches_plain():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "PP-OK" in out.stdout, out.stdout + "\n" + out.stderr
