"""Input specifications for every (architecture × input shape) pair.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no device allocation) for every model input of the workload, and
the matching PartitionSpecs, which the lowering tests
(tests/test_lowering_modes.py) compile against.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.models import model as model_lib
from repro.optim import adamw
from repro.train import sharding as sh
from repro.train.train_step import TrainConfig

Array = jax.Array
SDS = jax.ShapeDtypeStruct


# Per-shape config overrides (DESIGN.md §4): zamba2's shared attention is
# windowed at the long-context shape.
SHAPE_OVERRIDES: dict[tuple[str, str], dict[str, Any]] = {
    ("zamba2-2.7b", "long_500k"): {"sliding_window": 4096},
}

# Microbatch counts for the train shape, keyed by parameter scale — keeps
# the per-device live activation set inside v5e HBM (DESIGN.md §5).
def default_microbatches(cfg: ModelConfig) -> int:
    n = cfg.param_count()
    if n >= 40e9:
        return 16
    if n >= 10e9:
        return 8
    if n >= 2e9:
        return 4
    return 1


def skip_reason(cfg: ModelConfig, shape: InputShape) -> str | None:
    """Returns a reason string when this (arch, shape) pair is skipped."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("pure full-attention architecture: 500k-token decode is not "
                "sub-quadratic/bounded-state (DESIGN.md §4 skip list)")
    return None


def apply_overrides(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    over = SHAPE_OVERRIDES.get((cfg.name, shape.name))
    return cfg.replace(**over) if over else cfg


def batch_template(cfg: ModelConfig, shape: InputShape) -> dict[str, SDS]:
    """ShapeDtypeStructs for the data batch of a train/prefill shape."""
    b, s = shape.global_batch, shape.seq_len
    batch = {"tokens": SDS((b, s), jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = SDS((b, cfg.n_patches, cfg.vision_dim),
                                    jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = SDS((b, cfg.n_frames, cfg.d_model), jnp.bfloat16)
    return batch


@dataclass
class LoweringSpec:
    """Everything needed to ``jit(...).lower(...)`` one workload."""
    kind: str                  # train | prefill | decode
    fn: Any                    # the function to jit
    args: tuple                # ShapeDtypeStruct args
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple = ()


def params_sds(cfg: ModelConfig) -> Any:
    return jax.eval_shape(
        lambda k: model_lib.init_params(cfg, k), jax.random.PRNGKey(0))


def make_lowering_spec(cfg: ModelConfig, shape: InputShape, mesh: Mesh, *,
                       microbatches: int | None = None,
                       tcfg: TrainConfig | None = None,
                       mode: str = "megatron") -> LoweringSpec:
    cfg = apply_overrides(cfg, shape)
    psds = params_sds(cfg)
    # zero_* activation sharding applies to train/prefill tracing only
    # (decode keeps the megatron/flash-decode layout).
    act_mode = mode if shape.kind in ("train", "prefill") else "megatron"
    act_mode = sh.resolve_mode(mesh, act_mode, shape.global_batch,
                               shape.seq_len)
    if act_mode == "zero_batch" and cfg.n_experts:
        # grouped-local MoE dispatch: one token group per device so the
        # argsort/scatter stay local and only the expert all-to-all crosses
        # devices (see models/moe.py docstring + §Perf).
        cfg = cfg.replace(moe_groups=int(mesh.devices.size))
    elif act_mode == "zero_seq" and cfg.n_experts:
        # groups align to (pod, data) batch rows; the sort spans the
        # model-sharded sequence within a row (16 devices, not 256+).
        cfg = cfg.replace(moe_groups=int(shape.global_batch))
    param_mode = "zero_seq" if act_mode == "zero_batch" else act_mode
    pspecs = sh.param_specs(psds, mesh=mesh, fsdp=(shape.kind == "train"),
                            mode=param_mode)
    block_specs = {k: pspecs[k] for k in ("blocks", "shared_attn", "encoder")
                   if isinstance(pspecs, dict) and k in pspecs}
    model_lib.set_activation_spec(sh.activation_spec(mesh, act_mode),
                                  block_specs or None,
                                  mesh if act_mode != "megatron" else None)
    pshard = sh.named(pspecs, mesh)

    if shape.kind == "train":
        from repro.train.train_step import make_train_step
        # zero modes shard activations over the whole mesh — the per-device
        # live set is already tiny, and each microbatch would re-gather
        # every ZeRO-sharded weight (measured ×n_mb collective traffic).
        mb = microbatches or (1 if act_mode != "megatron"
                              else default_microbatches(cfg))
        tcfg = tcfg or TrainConfig(microbatches=mb)
        opt_sds = jax.eval_shape(adamw.init, psds)
        # AdamWState: step is scalar; m/v mirror params
        opt_specs = type(opt_sds)(step=P(), m=pspecs, v=pspecs)
        opt_shard = sh.named(opt_specs, mesh)
        batch = batch_template(cfg, shape)
        bspecs = sh.data_specs(batch, mesh, mode=act_mode)
        bshard = sh.named(bspecs, mesh)
        fn = make_train_step(cfg, tcfg)
        return LoweringSpec(
            kind="train", fn=fn,
            args=(psds, opt_sds, batch),
            in_shardings=(pshard, opt_shard, bshard),
            out_shardings=(pshard, opt_shard, None),
            donate_argnums=(0, 1))

    # Inference: serve-mode parameters are bf16, model-sharded, replicated
    # over the batch axes.
    serve_psds = jax.tree.map(
        lambda x: SDS(x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
        psds)
    serve_pspecs = sh.param_specs(serve_psds, mesh=mesh, fsdp=False)
    serve_pshard = sh.named(serve_pspecs, mesh)

    if shape.kind == "prefill":
        batch = batch_template(cfg, shape)
        bshard = sh.named(sh.data_specs(batch, mesh, mode=act_mode), mesh)

        def prefill_fn(params, batch):
            return model_lib.prefill(cfg, params, batch, shape.seq_len)

        return LoweringSpec(
            kind="prefill", fn=prefill_fn,
            args=(serve_psds, batch),
            in_shardings=(serve_pshard, bshard),
            out_shardings=None)

    # decode
    cache_sds = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, shape.global_batch, shape.seq_len))
    cshard = sh.named(sh.cache_specs(cache_sds, mesh), mesh)
    tokens = SDS((shape.global_batch, 1), jnp.int32)
    tok_shard = sh.named(sh.data_specs({"t": tokens}, mesh), mesh)["t"]

    def decode_fn(params, cache, tokens):
        return model_lib.decode_step(cfg, params, cache, tokens)

    return LoweringSpec(
        kind="decode", fn=decode_fn,
        args=(serve_psds, cache_sds, tokens),
        in_shardings=(serve_pshard, cshard, tok_shard),
        out_shardings=(None, cshard),
        donate_argnums=(1,))


def lower(spec: LoweringSpec):
    jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                     out_shardings=spec.out_shardings,
                     donate_argnums=spec.donate_argnums)
    return jitted.lower(*spec.args)
