"""Sharding rules: parameter/activation PartitionSpecs for train and serve.

Two modes (DESIGN.md §5):

``mode="megatron"`` (paper-faithful baseline — TP over ``model``):
- Tensor parallelism over the ``model`` axis, FSDP over the ``data`` axis
  (training only), pure data parallelism over the ``pod`` axis.
- Attention projections are (D, H, hd): the head axis shards over ``model``
  when divisible, else the head_dim axis (GQA kv heads rarely divide 16),
  else replicate.
- MoE experts shard over ``model`` when E divides it (expert parallelism,
  phi3.5-moe), else d_ff Megatron-sharding inside each expert (mixtral).
- The embedding / lm_head table is (Vp, D) with vocab over ``model`` so the
  chunked cross-entropy keeps logits vocab-sharded.
- 1-D leaves (norms, biases, scalars) replicate.
The generic rule is greedy: prefer ``model`` on the *last* shardable dim
(contraction outputs), ``data`` on the first remaining shardable dim.
Leaves under a scanned "blocks" collection skip their leading layer dim.

``mode="zero_seq"`` (the §Perf optimization): ZeRO-3 + sequence parallelism.
The HLO analysis of the megatron baseline shows two pathologies: (a) when
head counts don't divide the 16-way axis the greedy rule shards head_dim —
a *contraction* dim of the attention-score einsum — so XLA all-reduces full
(B, KV, rep, q, S) score tensors every layer; (b) activations carry no
``model``-axis sharding, so backward re-gathers full (B, S, D)/(B, S, F)
tensors per layer.  zero_seq instead:
- activations shard (B → data, S → model) everywhere (sequence parallel);
  attention queries stay S-sharded, K/V are all-gathered per layer (small);
- weights are *storage*-sharded over both axes on whatever dims divide
  (pure ZeRO-3) and all-gathered per layer at use — for every assigned
  arch the per-layer weight gather ≪ the score/activation all-reduces it
  replaces;
- MoE expert weights keep expert-parallelism over ``model`` when E divides
  it (the all-to-all dispatch is already the cheap pattern);
- embedding/lm_head keep vocab over ``model`` (chunked CE unchanged).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def _greedy_spec(shape: tuple[int, ...], start: int, mesh_sizes: dict[str, int],
                 fsdp_axis: str | None) -> P:
    assign: list[Any] = [None] * len(shape)
    # model on the last shardable dim
    for i in reversed(range(start, len(shape))):
        if shape[i] % mesh_sizes["model"] == 0:
            assign[i] = "model"
            break
    if fsdp_axis:
        for i in range(start, len(shape)):
            if assign[i] is None and shape[i] % mesh_sizes[fsdp_axis] == 0:
                assign[i] = fsdp_axis
                break
    return P(*assign)


def _is_stacked(path: tuple) -> bool:
    keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    return "blocks" in keys


def _leaf_name(path: tuple) -> str:
    k = path[-1]
    return getattr(k, "key", getattr(k, "name", str(k)))


def param_specs(params_or_shapes: Any, *, mesh: Mesh,
                fsdp: bool = True, mode: str = "megatron") -> Any:
    """PartitionSpec pytree for a parameter pytree (arrays or
    ShapeDtypeStructs)."""
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fsdp_axis = "data" if (fsdp and "data" in mesh_sizes) else None

    def zero_rule(path, leaf):
        """ZeRO-3 storage sharding: big dims over model/data wherever they
        divide; embeddings keep vocab over model; MoE experts keep expert
        parallelism when E divides the model axis."""
        shape = leaf.shape
        name = _leaf_name(path)
        start = 1 if _is_stacked(path) else 0
        eff = shape[start:]
        if len(eff) <= 1:
            return P()
        if name in ("embed", "lm_head"):
            spec = [None] * len(shape)
            if shape[0] % mesh_sizes["model"] == 0:
                spec[0] = "model"
            if fsdp_axis and shape[1] % mesh_sizes[fsdp_axis] == 0:
                spec[1] = fsdp_axis
            return P(*spec)
        if name in ("w_gate", "w_up", "w_down") and len(eff) == 3 \
                and eff[0] % mesh_sizes["model"] == 0:
            spec = [None] * len(shape)
            spec[start] = "model"                  # expert parallel
            if fsdp_axis and eff[1] % mesh_sizes[fsdp_axis] == 0:
                spec[start + 1] = fsdp_axis
            return P(*spec)
        # generic ZeRO: model on the largest divisible dim, data on the
        # next largest remaining divisible dim
        spec = [None] * len(shape)
        order = sorted(range(start, len(shape)), key=lambda i: -shape[i])
        for i in order:
            if shape[i] % mesh_sizes["model"] == 0:
                spec[i] = "model"
                break
        if fsdp_axis:
            for i in order:
                if spec[i] is None and shape[i] % mesh_sizes[fsdp_axis] == 0:
                    spec[i] = fsdp_axis
                    break
        return P(*spec)

    def rule(path, leaf):
        shape = leaf.shape
        name = _leaf_name(path)
        start = 1 if _is_stacked(path) else 0
        eff = shape[start:]
        if len(eff) <= 1:
            return P()
        if name in ("embed", "lm_head"):
            spec = [None] * len(shape)
            if shape[0] % mesh_sizes["model"] == 0:
                spec[0] = "model"
            if fsdp_axis and shape[1] % mesh_sizes[fsdp_axis] == 0:
                spec[1] = fsdp_axis
            return P(*spec)
        if name == "router":
            # (L, D, E): E is small; shard D over fsdp only
            spec = [None] * len(shape)
            if fsdp_axis and shape[start] % mesh_sizes[fsdp_axis] == 0:
                spec[start] = fsdp_axis
            return P(*spec)
        if name in ("w_gate", "w_up", "w_down") and len(eff) == 3:
            # MoE expert weights (L, E, a, b)
            e = eff[0]
            spec = [None] * len(shape)
            if e % mesh_sizes["model"] == 0:
                spec[start] = "model"          # expert parallel
                if fsdp_axis and eff[1] % mesh_sizes[fsdp_axis] == 0:
                    spec[start + 1] = fsdp_axis
            else:
                # Megatron inside experts: shard the f dim over model
                f_dim = start + (2 if name != "w_down" else 1)
                other = start + (1 if name != "w_down" else 2)
                if shape[f_dim] % mesh_sizes["model"] == 0:
                    spec[f_dim] = "model"
                if fsdp_axis and shape[other] % mesh_sizes[fsdp_axis] == 0:
                    spec[other] = fsdp_axis
            return P(*spec)
        return _greedy_spec(shape, start, mesh_sizes, fsdp_axis)

    return jax.tree_util.tree_map_with_path(
        zero_rule if mode == "zero_seq" else rule, params_or_shapes)


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that shard the global batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_specs(batch_template: Any, mesh: Mesh,
               mode: str = "megatron") -> Any:
    """Batch arrays shard their leading dim over (pod, data); in zero_seq
    mode the sequence dim (dim 1) additionally shards over ``model``; in
    zero_batch mode the batch dim shards over ALL axes (pure ZeRO-DP)."""
    ax = batch_axes(mesh)
    model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    all_ax = ax + ("model",) if model > 1 else ax

    def rule(leaf):
        spec: list[Any] = [None] * len(leaf.shape)
        if (mode == "zero_batch" and leaf.shape
                and leaf.shape[0] % _prod(mesh, all_ax) == 0):
            spec[0] = all_ax
            return P(*spec)
        if leaf.shape and leaf.shape[0] % _prod(mesh, ax) == 0:
            spec[0] = ax if len(ax) > 1 else ax[0]
        if (mode == "zero_seq" and len(leaf.shape) >= 2
                and leaf.shape[1] % model == 0 and model > 1):
            spec[1] = "model"
        return P(*spec)

    return jax.tree.map(rule, batch_template)


def resolve_mode(mesh: Mesh, mode: str, global_batch: int,
                 seq_len: int = 0) -> str:
    """zero_batch needs B to divide the whole mesh; fall back to zero_seq
    (which needs S to divide the model axis; else megatron)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = sizes.get("model", 1)
    if mode == "zero_batch":
        full = _prod(mesh, batch_axes(mesh)) * model
        if global_batch % full == 0:
            return "zero_batch"
        mode = "zero_seq"
    if mode == "zero_seq" and seq_len and seq_len % model:
        return "megatron"
    return mode


def activation_spec(mesh: Mesh, mode: str = "megatron") -> P | None:
    """The (B, S, D) hidden-state constraint applied inside the forward
    pass.  zero_seq: batch over (pod, data), sequence over model.
    zero_batch: batch over every axis."""
    ax = batch_axes(mesh)
    if mode == "zero_batch":
        model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        all_ax = ax + ("model",) if model > 1 else ax
        return P(all_ax, None, None)
    if mode != "zero_seq":
        return None
    return P(ax if len(ax) > 1 else ax[0], "model", None)


def cache_specs(cache_template: Any, mesh: Mesh) -> Any:
    """Decode caches: batch dim over (pod, data); attention K/V sequence dim
    over ``model`` (flash-decode layout); SSM states shard their trailing
    head_dim over ``model`` when divisible."""
    ax = batch_axes(mesh)
    nbatch = _prod(mesh, ax)
    model = mesh.shape["model"] if "model" in mesh.axis_names else 1
    bspec = ax if len(ax) > 1 else (ax[0] if ax else None)

    def rule(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        if name in ("pos", "key_pos"):
            return P()
        spec: list[Any] = [None] * len(shape)
        if name in ("k", "v"):
            # (n, B, S, KV, hd)
            if shape[1] % nbatch == 0 and nbatch > 1:
                spec[1] = bspec
            if shape[2] % model == 0:
                spec[2] = "model"
            return P(*spec)
        # ssm state (L, B, H, K, P), conv (L, B, W-1, d_inner), shifts
        if len(shape) >= 2 and shape[1] % nbatch == 0 and nbatch > 1:
            spec[1] = bspec
        for i in reversed(range(2, len(shape))):
            if shape[i] % model == 0:
                spec[i] = "model"
                break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, cache_template)


def _prod(mesh: Mesh, axes: tuple[str, ...]) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def named(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))
