"""Pallas TPU kernel: fused Metropolis-Hastings acceptance (paper eq. 7).

One MH step for a batch of tokens: given the point log-densities of the
target and proposal at the current state and the candidate, accept with
probability min(1, q(z)p(c) / (q(c)p(z))).  Elementwise and trivially
parallel — the value of the kernel is *fusion*: acceptance, the ratio, the
log of the uniform and the select retire in one VMEM pass instead of five
HBM-roundtrip ops.

This standalone step remains for callers that compute their own point
densities; the sorted sampling pipeline goes further and fuses the whole
chain — proposal draw, density gathers and acceptance — with the
table-tile residency in ``repro.kernels.mhw_fused`` (DESIGN.md §5.1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend

DEFAULT_TILE_B = 4096


def _mh_accept_kernel(z_ref, cand_ref, lp_z_ref, lp_c_ref, lq_z_ref,
                      lq_c_ref, u_ref, out_ref):
    log_ratio = (lp_c_ref[...] - lp_z_ref[...]
                 + lq_z_ref[...] - lq_c_ref[...])
    accept = jnp.log(u_ref[...] + 1e-30) < log_ratio
    out_ref[...] = jnp.where(accept, cand_ref[...], z_ref[...]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def mh_accept(z, cand, log_p_z, log_p_cand, log_q_z, log_q_cand, u, *,
              tile_b: int = DEFAULT_TILE_B, interpret: bool | None = None):
    """Fused accept/reject: all inputs (B,); returns (B,) int32 new states."""
    b = z.shape[0]
    tile_b = min(tile_b, b)
    assert b % tile_b == 0
    grid = (b // tile_b,)
    spec = pl.BlockSpec((tile_b,), lambda i: (i,))
    return pl.pallas_call(
        _mh_accept_kernel,
        grid=grid,
        in_specs=[spec] * 7,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b,), jnp.int32),
        name="mh_accept",
        interpret=backend.interpret("mh_accept", requested=interpret,
                                    lowers=False),
    )(z, cand, log_p_z, log_p_cand, log_q_z, log_q_cand, u)
