"""MoE dispatch correctness: grouped/vmapped and shard_map all-to-all paths
must agree with the dense oracle (no-drop capacity) and with each other."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.registry import ARCHITECTURES
from repro.models import moe as moe_mod


@pytest.fixture(scope="module")
def moe_setup():
    cfg = reduced(ARCHITECTURES["mixtral-8x7b"]).replace(
        capacity_factor=8.0)     # no drops → dense oracle comparable
    p = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_sorted_dispatch_matches_dense_oracle(moe_setup):
    cfg, p, x = moe_setup
    out, aux = moe_mod.moe_block(cfg, p, x)
    ref = moe_mod.moe_block_dense_ref(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)
    assert float(aux) > 0.0


@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_dispatch_matches_global(moe_setup, groups):
    """Grouped-local dispatch == global dispatch when nothing is dropped
    (per-group capacity at cf=8 is ample)."""
    cfg, p, x = moe_setup
    out_global, _ = moe_mod.moe_block(cfg, p, x)
    out_grouped, _ = moe_mod.moe_block(
        cfg.replace(moe_groups=groups), p, x)
    np.testing.assert_allclose(np.asarray(out_grouped, np.float32),
                               np.asarray(out_global, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_capacity_drops_are_per_group(moe_setup):
    """With a tight capacity, grouped dispatch drops per group — outputs
    stay finite and bounded."""
    cfg, p, x = moe_setup
    tight = cfg.replace(capacity_factor=0.5, moe_groups=4)
    out, aux = moe_mod.moe_block(tight, p, x)
    assert bool(jnp.isfinite(out).all())
    assert bool(jnp.isfinite(aux))


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import reduced
    from repro.configs.registry import ARCHITECTURES
    from repro.models import layers, moe as moe_mod

    # 4 experts over model axis of size 4 (divides); mesh (2, 4) = 8 devices
    cfg = reduced(ARCHITECTURES["mixtral-8x7b"]).replace(
        capacity_factor=8.0, moe_groups=8)
    p = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                          jnp.float32)

    # Reference: single-device global dispatch (no mesh, no act spec).
    ref, _ = moe_mod.moe_block(cfg.replace(moe_groups=0), p, x)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    layers.set_activation_spec(P(("data", "model"), None, None), None, mesh)
    try:
        with mesh:
            fn = jax.jit(lambda p, x: moe_mod.moe_block(cfg, p, x)[0])
            out = fn(p, x)
    finally:
        layers.set_activation_spec(None)
    got = np.asarray(out, np.float32)
    refn = np.asarray(ref, np.float32)
    err = np.abs(got - refn).max()
    assert err < 5e-2, f"shard_map MoE diverges from reference: {err}"
    print("SHARD_MAP_MOE_OK", err)
""")


@pytest.mark.slow
def test_shard_map_a2a_matches_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARD_MAP_MOE_OK" in proc.stdout
