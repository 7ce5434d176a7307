"""Jitted wrapper for the fused reservoir step (padding + scan driver)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.reservoir_step.reservoir_step import reservoir_step


def _pad_dim(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


class FusedReservoir:
    """Run a whole input sequence through the fused Pallas step via scan."""

    def __init__(self, w: np.ndarray, w_in: np.ndarray, leak: float = 1.0,
                 block: int = 128):
        self.dim = w.shape[0]
        self.block = block
        self.leak = float(leak)
        wp = _pad_dim(_pad_dim(jnp.asarray(w, jnp.float32), 0, block), 1, block)
        self.w = wp
        self.w_in = _pad_dim(jnp.asarray(w_in, jnp.float32), 1, block)

    def step(self, x: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
        """x: (B, dim), u: (B, I) -> (B, dim)."""
        xp = _pad_dim(x, 1, self.block)
        nxt = reservoir_step(xp, self.w, u, self.w_in, leak=self.leak,
                             block_r=self.block, block_c=self.block)
        return nxt[:, : self.dim]

    def run(self, inputs: jnp.ndarray, x0: jnp.ndarray | None = None
            ) -> jnp.ndarray:
        """inputs: (T, B, I) -> states (T, B, dim)."""
        t, b, _ = inputs.shape
        if x0 is None:
            x0 = jnp.zeros((b, self.dim), jnp.float32)

        def body(x, u):
            nxt = self.step(x, u)
            return nxt, nxt

        _, states = jax.lax.scan(body, x0, inputs)
        return states
