"""Pallas TPU kernel: digit-plane gemv for fixed integer matrices.

TPU-native form of the paper's bit-serial multiplier (Sec. III): the fixed
matrix is decomposed offline into signed digit planes ``d_w in {-1,0,1}``
(PN or CSD, see ``repro.core.bitplanes``), and

    y = x @ V  =  sum_w  (x @ d_w) << w

Each plane product is an int8 x int8 -> int32 matmul that maps directly onto
the MXU; the plane loop is a *static* Python loop, so planes whose block is
all-zero can be culled at trace time — the MXU-granular analogue of the
paper's constant propagation ("we can cull the AND gate ... and replace the
adder with a single flip-flop").

Grid: ``(C/bc, R/br)`` with the reduction dimension innermost; the output
block is revisited across the reduction steps and accumulated in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_interpret

DEFAULT_BLOCK_R = 128
DEFAULT_BLOCK_C = 128


def _kernel(x_ref, dig_ref, o_ref, *, width: int, plane_mask: tuple):
    """One (batch, bc) output tile; accumulates over the R grid dimension.

    plane_mask[w] is a trace-time constant: False planes (all-zero in this
    whole matrix) are culled from the unrolled loop entirely.
    """
    r_step = pl.program_id(1)

    @pl.when(r_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.int32)  # (B, br)
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for w in range(width):
        if not plane_mask[w]:
            continue  # trace-time constant propagation
        d = dig_ref[w].astype(jnp.int32)  # (br, bc)
        acc = acc + ((x @ d) << w)
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "plane_mask"))
def bitplane_gemv(
    x: jnp.ndarray,
    digits: jnp.ndarray,
    *,
    block_r: int = DEFAULT_BLOCK_R,
    block_c: int = DEFAULT_BLOCK_C,
    plane_mask: tuple | None = None,
) -> jnp.ndarray:
    """``y[b, c] = sum_r x[b, r] * V[r, c]`` via digit planes.

    Args:
        x: (B, R) int8/int32 activations (R divisible by block_r).
        digits: (W, R, C) int8 planes in {-1, 0, 1} with V = sum 2^w digits[w].
        plane_mask: per-plane keep flags (None keeps all planes).

    Returns:
        (B, C) int32 exact integer product.
    """
    b, r = x.shape
    w, r2, c = digits.shape
    assert r == r2, (x.shape, digits.shape)
    assert r % block_r == 0 and c % block_c == 0, "pad R/C to block multiples"
    if plane_mask is None:
        plane_mask = tuple([True] * w)

    grid = (c // block_c, r // block_r)
    return pl.pallas_call(
        functools.partial(_kernel, width=w, plane_mask=tuple(plane_mask)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_r), lambda ci, ri: (0, ri)),
            pl.BlockSpec((w, block_r, block_c), lambda ci, ri: (0, ri, ci)),
        ],
        out_specs=pl.BlockSpec((b, block_c), lambda ci, ri: (0, ci)),
        interpret=pallas_interpret(),
    )(x, digits)
