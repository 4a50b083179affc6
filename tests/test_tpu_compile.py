"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed here even without a chip: compiling for a
*described* v5e topology catches what interpret mode cannot — block shapes
Mosaic refuses, ops it cannot lower, kernels over the VMEM limit — at the
widths the chip smoke runs (LDA K=1024 over a 131072-type vocabulary row
range, tiles as the family picks them).  Each test asserts the kernel was
lowered (``tpu_custom_call``), not interpreted.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import family
from repro.core.lda import LDAConfig
from repro.core.pdp import PDPConfig
from repro.kernels import alias_build, mhw_fused

K, V = 1024, 131072
TILE_K = 128
STEPS = 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _tiles(cfg):
    fam = family.family_of(cfg)
    return fam.sorted_tile_v(cfg), fam.sorted_tile_b(cfg)


def _assert_lowered(lowered, kernel):
    """Lowered, and the device op carries the kernel's ``pallas_call``
    name, which profiles and the chip benchmark's prefixes match on."""
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call\(", text)


@pytest.mark.parametrize("n_tokens", [262144, 64],
                         ids=["training-chunk", "one-document"])
def test_mhw_sweep_fused_lowers(one_chip, n_tokens):
    """LDA/HDP fused sweep at the chip smoke's tiles: a training chunk
    (4096 docs x 64 positions) and a one-document serving chunk."""
    tile_v, tile_b = _tiles(LDAConfig(n_topics=K, vocab_size=V,
                                      tile_k=TILE_K))
    f32, i32 = jnp.float32, jnp.int32
    nb = max(1, n_tokens // tile_b)
    args = _shapes(one_chip, ((V, K), f32), ((V, K), i32), ((V,), f32),
                   ((V, K), f32), ((V, K), f32), ((K,), f32), ((K,), f32),
                   ((n_tokens,), i32), ((n_tokens,), i32),
                   ((n_tokens, K), f32), ((STEPS, n_tokens), i32),
                   *[((STEPS, n_tokens), f32)] * 4, *[((nb,), i32)] * 2)
    _assert_lowered(mhw_fused.mhw_sweep_fused.lower(
        *args, tile_v=tile_v, tile_b=tile_b, tile_k=TILE_K, n_steps=STEPS,
        beta=0.01, beta_bar=0.01 * V, interpret=False), "mhw_sweep_fused")


def test_pdp_sweep_fused_lowers(one_chip):
    """PDP fused sweep over its 2K joint outcomes at K=1024."""
    tile_v, tile_b = _tiles(PDPConfig(n_topics=K, vocab_size=V,
                                      tile_k=TILE_K))
    f32, i32 = jnp.float32, jnp.int32
    e, b = 2 * K, 16384
    args = _shapes(one_chip, ((V, e), f32), ((V, e), i32), ((V,), f32),
                   ((V, e), f32), ((V, K), f32), ((V, K), f32),
                   ((K,), f32), ((K,), f32), ((513, 513), f32), ((e,), f32),
                   ((b,), i32), ((b,), i32), ((b, K), f32),
                   ((STEPS, b), i32), *[((STEPS, b), f32)] * 4,
                   *[((b // tile_b,), i32)] * 2)
    _assert_lowered(mhw_fused.pdp_sweep_fused.lower(
        *args, tile_v=tile_v, tile_b=tile_b, tile_k=TILE_K, n_steps=STEPS,
        interpret=False), "pdp_sweep_fused")


def test_alias_build_fused_lowers(one_chip):
    """The LM-dense full alias build over the whole (V, K) table at the
    row tile ``pick_tile_r`` gives K=1024."""
    args = _shapes(one_chip, ((V, K), jnp.float32), ((K,), jnp.float32),
                   ((K,), jnp.float32))
    _assert_lowered(alias_build.alias_build_fused.lower(
        *args, beta=0.01, beta_bar=0.01 * V, interpret=False),
        "alias_build_fused")


def test_alias_build_gather_fused_lowers(one_chip):
    """The incremental alias rebuild of 64 changed rows at K=1024, on the
    full build's row tiles."""
    args = _shapes(one_chip, ((V, K), jnp.float32), ((K,), jnp.float32),
                   ((K,), jnp.float32), ((64,), jnp.int32))
    _assert_lowered(alias_build.alias_build_gather_fused.lower(
        *args, beta=0.01, beta_bar=0.01 * V, interpret=False),
        "alias_build_gather_fused")


def test_alias_build_rows_lowers(one_chip):
    """The generic incremental rebuild over a compacted row block."""
    args = _shapes(one_chip, ((64, K), jnp.float32))
    _assert_lowered(alias_build.alias_build_rows.lower(*args,
                                                       interpret=False),
                    "alias_build")
