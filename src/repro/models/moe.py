"""Mixture-of-Experts layer: top-k routing with capacity-bounded, sort-based
dispatch (TPU-native; no dense (T, E, C) one-hot dispatch tensors).

Two sharding regimes, chosen by the config (see ``repro.train.sharding``):

* expert-parallel (phi3.5-moe: E=16 divides the model axis) — expert weights
  sharded on the expert dim; the (E, C, D) dispatch buffer crosses from
  token-sharding (data) to expert-sharding (model), which XLA lowers to an
  all-to-all — the communication pattern the paper's parameter-server
  analysis stresses for sparse models.
* tensor-parallel experts (mixtral: E=8 does not divide 16) — every expert's
  d_ff is Megatron-sharded over the model axis; no all-to-all, one psum.

Dispatch algorithm (static shapes throughout):
  1. router logits → top-k experts + weights per token;
  2. flatten (token, slot) pairs, sort by expert id;
  3. position-in-expert via sorted-order cumsum; tokens beyond the per-expert
     capacity C = ceil(T·k/E · capacity_factor) are *dropped* (standard
     Switch/GShard semantics; the router aux loss keeps loads balanced);
  4. scatter into the (E, C, D) buffer, batched expert matmuls, scatter back
     weighted by router gates.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import cast, get_activation_spec, get_mesh

Array = jax.Array
Params = dict[str, Any]


def init_moe(cfg: ModelConfig, key: Array) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_out,
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(8, ((c + 7) // 8) * 8)  # pad to an 8-multiple for tiling


def _dispatch(cfg: ModelConfig, xt: Array, gate_vals: Array,
              expert_ids: Array, c: int) -> tuple[Array, Array, Array, Array]:
    """Sort-based dispatch of ONE token group: (T', D) → (E, C, D) buffer
    plus (slot, keep, sorted_token/gate) combine metadata."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    flat_expert = expert_ids.reshape(-1)                      # (T'*k,)
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert)                          # stable
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    # position of each (token, slot) within its expert's queue
    counts = jnp.zeros((e,), jnp.int32).at[sorted_expert].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(t * k, dtype=jnp.int32) - offsets[sorted_expert]
    keep = pos_in_expert < c

    # scatter tokens into the (E, C, D) buffer
    slot = jnp.where(keep, sorted_expert * c + pos_in_expert, e * c)
    buf = jnp.zeros((e * c + 1, d), xt.dtype).at[slot].set(xt[sorted_token])
    return buf[:-1].reshape(e, c, d), slot, keep, (sorted_token, sorted_gate)


def _combine(out_buf: Array, slot: Array, keep: Array, meta, t: int,
             dtype) -> Array:
    """Scatter expert outputs of one group back to (T', D) token order."""
    sorted_token, sorted_gate = meta
    e, c, d = out_buf.shape
    gathered = out_buf.reshape(e * c, d)[jnp.where(keep, slot, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    return jnp.zeros((t, d), dtype).at[sorted_token].add(
        gathered * sorted_gate[:, None].astype(dtype))




def _constrain_dispatch(buf: Array) -> Array:
    """(G, E, C, D): pin G over the FULL device grid (scatter stays local).

    Without this, consumer propagation pushes expert-sharding into the
    dispatch scatter whose indices are data-dependent — XLA then replicates
    the scattered operand (measured 32 GiB/layer all-gathers).  Pinning the
    buffer local leaves exactly one reshard (G releases the model axis, E
    acquires it) at the einsum below.  Constraining E over model here
    instead triggers SPMD full-rematerialization — measured 3.4× worse."""
    act = get_activation_spec()
    if act is None:
        return buf
    ax = act[0] if isinstance(act[0], tuple) else (act[0],)
    g_ax = ax if "model" in ax else ax + ("model",)
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(buf, P(g_ax, None, None, None))


def _moe_a2a(cfg: ModelConfig, p: Params, xg: Array, gateg: Array,
             idsg: Array, c: int, mesh, dtype) -> Array:
    """Expert-parallel MoE with explicit all-to-all (shard_map).

    One token group per device (G = mesh size, group g on device g):
      1. device-local sort-based dispatch → (E, C, D);
      2. ``all_to_all`` over the model axis: each model-rank keeps its
         E/m experts and receives their tokens from all peers →
         (E/m, m·C, D);
      3. expert MLPs at jit level — buf (E@model, ·@rest, D) is already
         aligned with the expert-sharded weights, zero collectives;
      4. reverse all_to_all + device-local combine.
    This is the paper's client→server key routing made physical: tokens
    (updates) travel to the shard that owns their expert (parameter row),
    compute happens there, results return — two all-to-alls of exactly
    the dispatched bytes, nothing replicated.
    """
    from jax.sharding import PartitionSpec as P

    g, tg, d = xg.shape
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes["model"]
    e = cfg.n_experts
    rest = tuple(a for a in mesh.axis_names if a != "model")
    all_ax = tuple(mesh.axis_names)
    g_spec = P(all_ax, None, None)
    meta_spec = P(all_ax, None)

    def dispatch(xx, gg, ii):
        buf, slot, keep, (st, sg) = _dispatch(cfg, xx[0], gg[0], ii[0], c)
        buf2 = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                  tiled=True)              # (E/m, m·C, D)
        return buf2, slot[None], keep[None], st[None], sg[None]

    buf2, slot, keep, st, sg = jax.shard_map(
        dispatch, mesh=mesh,
        in_specs=(g_spec, g_spec, g_spec),
        out_specs=(P("model", rest, None), meta_spec, meta_spec, meta_spec,
                   meta_spec),
        check_vma=False,
    )(xg, gateg, idsg)

    # Expert MLPs: buf2 (E@model, CC@rest, D) × weights (E@model, ·, ·) —
    # expert dims aligned, no collectives.
    gate_h = jnp.einsum("ecd,edf->ecf", buf2, cast(p["w_gate"]),
                        preferred_element_type=jnp.float32)
    up_h = jnp.einsum("ecd,edf->ecf", buf2, cast(p["w_up"]),
                      preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate_h) * up_h).astype(dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", h, cast(p["w_down"]),
                         preferred_element_type=jnp.float32).astype(dtype)

    def combine(ob, sl, kp, stt, sgg):
        back = jax.lax.all_to_all(ob, "model", split_axis=1, concat_axis=0,
                                  tiled=True)               # (E, C, D)
        out = _combine(back, sl[0], kp[0], (stt[0], sgg[0]), tg, dtype)
        return out[None]

    out = jax.shard_map(
        combine, mesh=mesh,
        in_specs=(P("model", rest, None), meta_spec, meta_spec, meta_spec,
                  meta_spec),
        out_specs=g_spec,
        check_vma=False,
    )(out_buf, slot, keep, st, sg)
    return out


def moe_block(cfg: ModelConfig, p: Params, x: Array
              ) -> tuple[Array, Array]:
    """x: (B, S, D) → (out, aux_loss).

    Dispatch is GROUPED (``cfg.moe_groups`` token groups, vmapped): each
    group sorts/paks its own tokens with a per-group capacity.  With groups
    aligned to the device grid (zero modes set G = mesh size) the argsort,
    scatter and combine are all device-LOCAL and the only cross-device
    movement is the (G, E, C, D) → expert-sharded buffer reshard — the
    all-to-all that expert parallelism actually requires.  A single global
    sort (G=1) makes the token permutation span all devices and XLA falls
    back to replicate+all-reduce of (T·k, D) dispatch tensors — measured
    64 GiB/layer on phi3.5-moe (§Perf).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    g = cfg.moe_groups or 1
    if t % g:
        g = 1
    tg = t // g
    c = capacity(cfg, tg)
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt, cast(p["router"]),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # Load-balance auxiliary loss (Switch-style): E * Σ_e f_e · P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight

    # ---- grouped local dispatch ----------------------------------------
    xg = xt.reshape(g, tg, d)
    gateg = gate_vals.reshape(g, tg, k)
    idsg = expert_ids.reshape(g, tg, k)

    mesh = get_mesh()
    act = get_activation_spec()
    batch_covers_model = (act is not None and isinstance(act[0], tuple)
                          and "model" in act[0])
    if (mesh is not None and "model" in mesh.axis_names
            and batch_covers_model     # zero_batch: groups align 1:1 devices
            and g == int(mesh.devices.size)
            and e % dict(zip(mesh.axis_names, mesh.devices.shape))["model"] == 0):
        # shard_map path: device-local dispatch + explicit all_to_all.
        # Plain-jit alternatives all fail (measured, §Perf): XLA either
        # replicates the data-dependent scatter (32 GiB/layer all-gathers)
        # or full-remats the constrained reshard.
        out = _moe_a2a(cfg, p, xg, gateg, idsg, c, mesh, x.dtype)
        return out.reshape(b, s, d), aux

    buf, slot, keep, meta = jax.vmap(
        lambda xx, gg, ii: _dispatch(cfg, xx, gg, ii, c))(xg, gateg, idsg)
    # buf: (G, E, C, D) — G sharded over the device grid, E to be
    # expert-sharded by the einsum below (the all-to-all boundary).
    buf = _constrain_dispatch(buf)

    # ---- batched expert MLPs (E-sharded weights) ------------------------
    if g == 1:
        # 3-D form: XLA:CPU's DotThunk executes this (tests/examples); the
        # 4-D grouped form below is compile-only on CPU (dry-run).
        b3 = buf[0]
        gate_h = jnp.einsum("ecd,edf->ecf", b3, cast(p["w_gate"]),
                            preferred_element_type=jnp.float32)
        up_h = jnp.einsum("ecd,edf->ecf", b3, cast(p["w_up"]),
                          preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate_h) * up_h).astype(x.dtype)
        out_buf = jnp.einsum("ecf,efd->ecd", h, cast(p["w_down"]),
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)[None]
    else:
        gate_h = jnp.einsum("gecd,edf->gecf", buf, cast(p["w_gate"]),
                            preferred_element_type=jnp.float32)
        up_h = jnp.einsum("gecd,edf->gecf", buf, cast(p["w_up"]),
                          preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate_h) * up_h).astype(x.dtype)
        out_buf = jnp.einsum("gecf,efd->gecd", h, cast(p["w_down"]),
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)

    # ---- combine back (group-local) -------------------------------------
    out = jax.vmap(
        lambda ob, sl, kp, mt: _combine(ob, sl, kp, mt, tg, x.dtype))(
        out_buf, slot, keep, meta)
    return out.reshape(b, s, d), aux


def moe_block_dense_ref(cfg: ModelConfig, p: Params, x: Array) -> Array:
    """Oracle: evaluate every expert on every token and mix by gates
    (no capacity drops).  Used by tests on small shapes."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    gate_h = jnp.einsum("td,edf->etf", xt, p["w_gate"].astype(x.dtype))
    up_h = jnp.einsum("td,edf->etf", xt, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(gate_h) * up_h
    all_out = jnp.einsum("etf,efd->etd", h, p["w_down"].astype(x.dtype))

    mask = jax.nn.one_hot(expert_ids, cfg.n_experts, dtype=jnp.float32)
    weights = jnp.einsum("tk,tke->te", gate_vals, mask)       # (T, E)
    out = jnp.einsum("te,etd->td", weights.astype(x.dtype), all_out)
    return out.reshape(b, s, d)
