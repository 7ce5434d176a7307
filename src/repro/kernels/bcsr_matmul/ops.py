"""ExecutionPlan -> sorted/padded BCSR tile list -> Pallas kernel.

The tile sort, empty-column padding and gather all live in
:class:`repro.plan.BcsrLayout`; this wrapper only pads activations and
dispatches.  It accepts a FixedMatrix / ExecutionPlan (the shared compile
path) or a bare BlockSparse (standalone block-sparse matmuls).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.sparse import BlockSparse, FixedMatrix
from repro.kernels.bcsr_matmul.bcsr_matmul import bcsr_matmul
from repro.plan import BcsrLayout, ExecutionPlan, plan_for


class BcsrMatmul:
    """Precompiled block-sparse multiplier over one static tile layout."""

    def __init__(self,
                 source: FixedMatrix | ExecutionPlan | BlockSparse | BcsrLayout):
        if isinstance(source, BcsrLayout):
            layout = source
        elif isinstance(source, ExecutionPlan):
            layout = source.bcsr
        elif isinstance(source, FixedMatrix):
            layout = plan_for(source).bcsr
        else:
            layout = BcsrLayout.from_blocks(source)
        self.layout = layout

    # Everything static lives on the layout; expose the public surface
    # as read-only views instead of mirrored copies.
    block = property(lambda self: self.layout.block)
    shape = property(lambda self: self.layout.shape)
    rows_pad = property(lambda self: self.layout.rows_pad)
    cols_pad = property(lambda self: self.layout.cols_pad)
    data = property(lambda self: self.layout.data)
    cols = property(lambda self: self.layout.cols)
    rows = property(lambda self: self.layout.rows)
    n_tiles = property(lambda self: self.layout.n_tiles)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, r = x.shape
        assert r == self.shape[0], (x.shape, self.shape)
        if r != self.rows_pad:
            x = jnp.pad(x, ((0, 0), (0, self.rows_pad - r)))
        y = bcsr_matmul(x, self.data, self.cols, self.rows, self.cols_pad,
                        block=self.block)
        return y[:, : self.shape[1]]
