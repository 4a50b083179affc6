"""What the chip benchmark's own tests share (they run on the CPU).

A module of its own name, not ``conftest``: the repository's ``tests``
directory has a ``conftest`` too, and which of the two ``import conftest``
finds depends on the order pytest loads them.
"""

from __future__ import annotations

import importlib.util
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]

# Appended, not prepended: the benchmark's directory holds a ``tests``
# directory, and ahead of the repository root it would shadow the repo's
# own ``tests`` namespace package (``from tests.conftest import ...``).
for path in (CHIP, REPO / "src"):
    if str(path) not in sys.path:
        sys.path.append(str(path))

# A cell small enough for interpreted kernels on the CPU, on the same
# driver, reference and readers as the chip cells.
TINY_MODEL = {"n_topics": 16, "vocab_size": 256, "alpha": 0.1, "beta": 0.01,
              "mh_steps": 2, "sorted_chunks": 2}
TINY_CORPUS = {"n_docs": 24, "doc_len": 32, "min_len": 16,
               "theta_conc": 0.2, "zipf_a": 1.2}


class FakeChip:
    """What the harness reads of a device, for runs on the CPU."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self) -> dict:
        return {"peak_bytes_in_use": 1}


def load_run(root: pathlib.Path):
    """The ``run`` module of the benchmark copy under ``root``."""
    path = root / "benchmarks" / "chip" / "run.py"
    spec = importlib.util.spec_from_file_location(f"chip_run_{id(root)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
