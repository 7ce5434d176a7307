"""Whether a Pallas kernel runs compiled or interpreted, from the platform.

Pallas lowers to Mosaic on the TPU.  The CPU backend has no Pallas
lowering, so there the same kernels run in the Pallas interpreter (tests,
examples, CPU rehearsals).  The choice is derived here, once, from
``jax.default_backend()`` and is never a caller option: a TPU run can
therefore never report a device it did not use for the hot path.  A
platform with no rule is refused rather than guessed.
"""

from __future__ import annotations

import jax

_INTERPRET = {"cpu": True, "tpu": False}


def interpret_for(platform: str) -> bool:
    """The ``interpret=`` value of every ``pallas_call`` on ``platform``."""
    try:
        return _INTERPRET[platform]
    except KeyError:
        raise RuntimeError(
            f"no Pallas execution rule for platform {platform!r}; "
            f"known platforms: {sorted(_INTERPRET)}") from None


def pallas_interpret() -> bool:
    """``interpret=`` for the platform JAX runs on (``jax.default_backend()``)."""
    return interpret_for(jax.default_backend())
