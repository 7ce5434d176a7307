"""Pallas TPU kernel: block-sparse (BCSR) matmul with static culling.

``y = x @ M`` where M's nonzero-block structure is *fixed* (the paper's
setting: the reservoir matrix never changes).  The grid iterates only the
nonzero blocks — zero blocks are culled before the kernel is even launched,
exactly as the paper's synthesis flow culls adders for zero weights.  Block
coordinates arrive via scalar prefetch so the BlockSpec index maps can
gather the right x / output tiles per step.

The block list must be sorted by (col, row): the output tile for a column
is then revisited on consecutive grid steps and accumulates in VMEM.
Columns with no nonzero blocks are padded with one zero block so every
output tile gets initialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_interpret


def _kernel(cols_ref, rows_ref, x_ref, blk_ref, o_ref):
    i = pl.program_id(0)
    is_first = i == 0
    prev = cols_ref[jnp.maximum(i - 1, 0)]
    new_col = jnp.logical_or(is_first, cols_ref[i] != prev)

    @pl.when(new_col)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    blk = blk_ref[0]
    o_ref[...] += jax.lax.dot(x, blk.astype(x.dtype),
                              preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_cols", "block"))
def bcsr_matmul(
    x: jnp.ndarray,
    blocks: jnp.ndarray,
    block_cols: jnp.ndarray,
    block_rows: jnp.ndarray,
    out_cols: int,
    *,
    block: int = 128,
) -> jnp.ndarray:
    """Block-sparse product over a static structure.

    Args:
        x: (B, R) activations, R divisible by ``block``.
        blocks: (n_blk, block, block) nonzero tiles, sorted by (col, row),
            padded so every output column block appears at least once.
        block_cols / block_rows: (n_blk,) int32 tile coordinates.
        out_cols: C (divisible by ``block``).

    Returns:
        (B, C) in x.dtype's accumulation type (f32 for f32/bf16 in).
    """
    b, r = x.shape
    n_blk = blocks.shape[0]
    assert r % block == 0 and out_cols % block == 0
    out_dtype = jnp.float32 if x.dtype in (jnp.float32, jnp.bfloat16) else jnp.int32

    # Scalar-prefetch grid spec (TPU): coordinates available to index maps.
    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((b, block), lambda i, cols, rows: (0, rows[i])),
            pl.BlockSpec((1, block, block), lambda i, cols, rows: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, block), lambda i, cols, rows: (0, cols[i])),
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((b, out_cols), out_dtype),
        grid_spec=grid_spec,
        interpret=pallas_interpret(),
    )(block_cols, block_rows, x, blocks)
