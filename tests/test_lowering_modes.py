"""Lowering-path regression tests: every sharding mode must lower+compile
a reduced arch on a small forced-device mesh."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.configs.base import InputShape, reduced
    from repro.configs.registry import ARCHITECTURES
    from repro.launch import specs as specs_lib

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape_train = InputShape("tiny_train", seq_len=64, global_batch=8,
                             kind="train")
    shape_decode = InputShape("tiny_decode", seq_len=64, global_batch=8,
                              kind="decode")

    for arch in ("smollm-360m", "mixtral-8x7b", "rwkv6-3b"):
        cfg = reduced(ARCHITECTURES[arch]).replace(vocab_size=512)
        for mode in ("megatron", "zero_seq", "zero_batch"):
            with mesh:
                spec = specs_lib.make_lowering_spec(cfg, shape_train, mesh,
                                                    mode=mode)
                compiled = specs_lib.lower(spec).compile()
                assert compiled is not None
        with mesh:
            spec = specs_lib.make_lowering_spec(cfg, shape_decode, mesh)
            specs_lib.lower(spec).compile()
        print(f"LOWERED {arch}")
    print("ALL_MODES_OK")
""")


@pytest.mark.slow
def test_all_sharding_modes_lower():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ALL_MODES_OK" in proc.stdout
