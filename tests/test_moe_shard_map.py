"""shard_map expert-parallel MoE: equivalence with the GSPMD path.

Needs >1 device, so it runs in a subprocess with
--xla_force_host_platform_device_count=8 (the main test process locked
jax to 1 CPU device at import). The child is pinned to the CPU
(`JAX_PLATFORMS=cpu`): on a machine with an accelerator it must not
reach for a chip the parent may already hold.
"""
import os
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_shard_map_moe_matches_reference():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config
        from repro.models.moe_shard_map import moe_ffn_shard_map
        from repro.models.layers import moe_ffn
        from repro.models.transformer import _init_moe

        cfg = get_config("kimi_k2_1t_a32b", smoke=True)
        cfg = dataclasses.replace(cfg, n_experts=8, top_k=2,
                                  capacity_factor=8.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        p = _init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        with jax.set_mesh(mesh):
            out_sm, _ = jax.jit(lambda p_, x_: moe_ffn_shard_map(
                cfg, p_, x_, mesh, ("data",), "model"))(p, x)
        out_ref, _ = moe_ffn(cfg, p, x)
        err = float(jnp.abs(out_sm - out_ref).max())
        assert err < 1e-4, err
        print("OK", err)
    """) % (os.path.join(os.path.dirname(__file__), "..", "src"),)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout
