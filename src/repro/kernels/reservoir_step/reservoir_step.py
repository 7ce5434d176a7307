"""Pallas TPU kernel: fused reservoir state update.

One step of paper Eq. 1 for a batch of reservoir states:

    x' = (1 - leak) * x + leak * tanh(u @ W_in + x @ W)

Fusing the two matmuls with the activation keeps the (B, block_c) output
tile resident in VMEM across the whole reduction — the recurrent latency
path the paper optimizes is exactly this loop, so on TPU we avoid three
HBM round-trips per step (pre-activation, activation, blend).

Grid: ``(C/bc, R/br)``, reduction innermost.  The input projection
``u @ W_in`` joins the accumulation on the first reduction step; the
leak/tanh epilogue fires on the last.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_interpret


def _kernel(x_ref, w_ref, u_ref, win_ref, xold_ref, o_ref, *, leak: float,
            n_r: int):
    r_step = pl.program_id(1)

    @pl.when(r_step == 0)
    def _init():
        o_ref[...] = (u_ref[...] @ win_ref[...]).astype(o_ref.dtype)

    o_ref[...] += x_ref[...] @ w_ref[...]

    @pl.when(r_step == n_r - 1)
    def _epilogue():
        o_ref[...] = ((1.0 - leak) * xold_ref[...]
                      + leak * jnp.tanh(o_ref[...]))


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "leak"))
def reservoir_step(
    x: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    w_in: jnp.ndarray,
    *,
    leak: float = 1.0,
    block_r: int = 128,
    block_c: int = 128,
) -> jnp.ndarray:
    """Fused ESN update for a state batch.

    Args:
        x: (B, R) current states.
        w: (R, R) fixed reservoir matrix (R divisible by both blocks).
        u: (B, I) inputs.
        w_in: (I, R) input weights.

    Returns:
        (B, R) next states, float32.
    """
    b, r = x.shape
    i = u.shape[1]
    assert w.shape == (r, r) and w_in.shape == (i, r)
    assert r % block_r == 0 and r % block_c == 0
    n_r = r // block_r
    grid = (r // block_c, n_r)
    return pl.pallas_call(
        functools.partial(_kernel, leak=leak, n_r=n_r),
        out_shape=jax.ShapeDtypeStruct((b, r), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_r), lambda ci, ri: (0, ri)),       # x
            pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci)),  # w
            pl.BlockSpec((b, i), lambda ci, ri: (0, 0)),              # u
            pl.BlockSpec((i, block_c), lambda ci, ri: (0, ci)),       # w_in
            pl.BlockSpec((b, block_c), lambda ci, ri: (0, ci)),       # x (old)
        ],
        out_specs=pl.BlockSpec((b, block_c), lambda ci, ri: (0, ci)),
        interpret=pallas_interpret(),
    )(x, w, u, w_in, x)
