"""Pallas TPU kernels.

Each subpackage follows the <name>.py (pl.pallas_call + BlockSpec) /
ops.py (jit'd wrapper) / ref.py (pure-jnp oracle) convention.

Every kernel takes ``interpret=None`` and resolves it through
:func:`resolve_interpret`: compiled on a TPU, interpreted on the CPU backend
(the one the tests run on), refused anywhere else.
"""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    An explicit ``True``/``False`` is honoured as given (tests interpret on
    purpose; a compile for a described, unattached chip passes ``False``).
    ``None`` follows the default backend: a TPU compiles the kernel, the CPU
    backend interprets it, and any other platform raises — these kernels
    have no compiled lowering there, and quietly interpreting on an
    accelerator would time the interpreter instead of the chip.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU and interpret on the CPU "
        f"backend only; the default backend is {platform!r}"
    )
