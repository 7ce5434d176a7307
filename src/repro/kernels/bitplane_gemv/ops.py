"""ExecutionPlan -> padded digit planes -> Pallas gemv.

The digit decomposition, MXU padding and whole-plane cull mask all come
from the shared :mod:`repro.plan` lowering; this wrapper only pads the
activations and dispatches.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.sparse import FixedMatrix
from repro.kernels.bitplane_gemv.bitplane_gemv import bitplane_gemv
from repro.plan import ExecutionPlan, plan_for


def digits_from_fixed(fm: FixedMatrix) -> np.ndarray:
    """Signed digit planes (W, R, C) int8 via the shared ExecutionPlan."""
    return plan_for(fm).digits


class BitplaneGemv:
    """Precompiled digit-plane multiplier for one fixed matrix.

    Offline (init): pull the MXU-padded planes and the per-plane cull mask
    from the ExecutionPlan.  Online (``__call__``): one Pallas call, exact
    int32 result.
    """

    def __init__(self, source: FixedMatrix | ExecutionPlan,
                 block_r: int = 128, block_c: int = 128):
        plan = source if isinstance(source, ExecutionPlan) else plan_for(source)
        self.plan = plan
        self.digits = plan.padded_digits(block_r, block_c)
        self.rows, self.cols = plan.shape
        self.block_r, self.block_c = block_r, block_c
        # Whole-plane culling: CSD often leaves high planes empty.
        self.plane_mask = plan.plane_mask

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (B, rows) integer -> (B, cols) int32 exact."""
        b, r = x.shape
        assert r == self.rows, (x.shape, self.rows)
        pad = (-r) % self.block_r
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        y = bitplane_gemv(x, self.digits,
                          block_r=self.block_r, block_c=self.block_c,
                          plane_mask=self.plane_mask)
        return y[:, : self.cols]
