"""Which way a Pallas kernel runs: compiled, or through the interpreter.

The interpreter exists for the CPU, where no Pallas kernel can be compiled.
On an accelerator it would measure Python, so asking for it there is an
error rather than a silent slowdown."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` interprets exactly when the default backend is the CPU.
    ``True`` on any other backend raises ``ValueError``.  ``False`` is
    always allowed: it is how a kernel is lowered for a described TPU
    from a CPU process (tests/test_chip_compile.py)."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"Pallas interpret mode was requested on the "
            f"{jax.default_backend()!r} backend; it is for the CPU only")
    return bool(interpret)
