"""Where a Pallas kernel runs: the one place that decides interpretation.

The platform decides.  On CPU every kernel runs in Pallas interpret mode
(the test and development path); on TPU every kernel is lowered to Mosaic
and never interpreted.  A kernel that has no TPU lowering yet raises on
TPU instead of silently falling back to the interpreter.

``interpret=False`` on a CPU host is the one override, and it only makes
sense for ahead-of-time compiles against a described TPU topology
(``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import jax


def interpret(kernel: str, *, requested: bool | None = None,
              lowers: bool = True) -> bool:
    """Whether ``kernel`` runs interpreted on the current default backend.

    ``requested`` is the caller's explicit choice (None = the platform
    decides).  ``lowers=False`` marks a kernel whose body Mosaic cannot
    compile yet: it raises on TPU.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True if requested is None else bool(requested)
    if platform == "tpu":
        if not lowers:
            raise NotImplementedError(
                f"Pallas kernel {kernel!r} has no TPU lowering; it runs only "
                "in interpret mode on CPU")
        if requested:
            raise ValueError(f"Pallas kernel {kernel!r}: interpret mode is "
                             "refused on TPU")
        return False
    raise NotImplementedError(
        f"Pallas kernels support CPU (interpreted) and TPU, not {platform!r}")
