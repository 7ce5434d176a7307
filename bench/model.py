"""The benchmark's own reservoir: weights from a seed, and the plain reference.

Nothing here imports the system under test.  The weights are made by the
benchmark and handed to both sides: the program compiles them through its
own offline lowering, and :func:`reference_preds` rolls them with plain
``jax.numpy`` at the highest matmul precision.  The recurrence follows the
configuration's stated arithmetic (paper Sec. II, Eq. 1-2; integer state
as in the paper's ref. [16]):

    xq(n-1) = clip(round(x(n-1) * smax), -smax - 1, smax)        8-bit state
    x(n)    = (1 - leak) x(n-1)
              + leak * tanh(u(n) W_in + (xq(n-1) @ Q) * scale / smax)
    y(n)    = x(n) W_out

with ``Q`` the matrix quantized to ``weight_bits`` signed integers and
``scale = max|W| / (2**(weight_bits-1) - 1)``.  The integer product is
exact; the float products run at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# one independent random stream per purpose, so adding a draw to one never
# moves another
STREAMS = {"weights": 1, "readout": 2, "traffic": 3, "signal": 4,
           "sample": 5}

# the reference one precision step below the configuration's, for the
# check's control (keywords of ``reference_preds``): int4 for the int8
# state and weights, bfloat16 operands for the float32 input projection
# and readout
CONTROLS = {"int4_states": {"state_bits": 4},
            "int4_weights": {"weight_bits": 4},
            "bf16_dots": {"float_dots": "bfloat16"}}

REF_ROWS = 64           # requests per batch of the reference rollout
TRAIN_STEPS = 1024      # readout fit: next-step prediction on a seeded signal
WASHOUT = 128
RIDGE = 1e-2


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """The generator of one purpose for one seed (any whole number)."""
    return np.random.default_rng([abs(int(seed)), STREAMS[stream]])


@dataclasses.dataclass(frozen=True)
class Weights:
    """One reservoir, as the configuration builds it."""

    dense: np.ndarray       # (R, R) float64: the matrix handed to the program
    w_in: np.ndarray        # (I, R) float32
    w_out: np.ndarray       # (R, O) float32: ridge readout
    q: np.ndarray           # (R, R) int8: ``dense`` quantized
    scale: float            # dense ~= q * scale


def quantize(dense: np.ndarray, bits: int) -> tuple[np.ndarray, float]:
    """Symmetric per-matrix quantization to signed ``bits``-bit integers."""
    qmax = (1 << (bits - 1)) - 1
    amax = float(np.abs(dense).max())
    scale = amax / qmax if amax > 0 else 1.0
    q = np.clip(np.round(dense / scale), -qmax - 1, qmax)
    return q.astype(np.int8), scale


def spectral_radius(m: np.ndarray, rng: np.random.Generator) -> float:
    """Largest eigenvalue magnitude (ARPACK, with a seeded start vector so
    the result repeats)."""
    import scipy.sparse.linalg as sla
    v0 = rng.standard_normal(m.shape[0])
    vals = sla.eigs(m, k=1, which="LM", v0=v0, return_eigenvectors=False,
                    maxiter=m.shape[0] * 20)
    return float(np.abs(vals[0]))


def signal(rng: np.random.Generator, n: int) -> np.ndarray:
    """A seeded sum of three sinusoids plus noise, (n + 1,) float32."""
    t = np.arange(n + 1)
    freq = rng.uniform(0.01, 0.05, 3)
    phase = rng.uniform(0.0, 2 * np.pi, 3)
    s = sum(a * np.sin(2 * np.pi * f * t + p)
            for a, f, p in zip((0.5, 0.3, 0.2), freq, phase))
    return (s + 0.02 * rng.standard_normal(n + 1)).astype(np.float32)


def rollout_np(cfg: dict, q: np.ndarray, scale: float, w_in: np.ndarray,
               u: np.ndarray) -> np.ndarray:
    """States (T, R) of one sequence, in numpy float64 (readout fitting
    only; the check uses :func:`reference_preds`)."""
    smax = (1 << (cfg["state_bits"] - 1)) - 1
    leak = cfg["leak"]
    qf = q.astype(np.float64)
    w_in = w_in.astype(np.float64)
    x = np.zeros(q.shape[0])
    out = np.empty((u.shape[0], q.shape[0]))
    for n in range(u.shape[0]):
        xq = np.clip(np.round(x * smax), -smax - 1, smax)
        pre = u[n] @ w_in + (xq @ qf) * (scale / smax)
        x = (1.0 - leak) * x + leak * np.tanh(pre)
        out[n] = x
    return out


def make_weights(cfg: dict) -> Weights:
    """The configuration's reservoir, made from its own ``seed``.

    Bernoulli element sparsity over uniform(-1, 1) entries, rescaled to
    the stated spectral radius; uniform input weights; a ridge readout
    fit on next-step prediction of a seeded signal.
    """
    r, i, o = cfg["reservoir_dim"], cfg["input_dim"], cfg["output_dim"]
    rng = rng_for(cfg["seed"], "weights")
    m = rng.uniform(-1.0, 1.0, size=(r, r))
    mask = rng.random((r, r)) >= cfg["element_sparsity"]
    dense = m * mask
    dense = dense * (cfg["spectral_radius"] / spectral_radius(dense, rng))
    w_in = rng.uniform(-cfg["input_scale"], cfg["input_scale"],
                       size=(i, r)).astype(np.float32)
    q, scale = quantize(dense, cfg["weight_bits"])

    rr = rng_for(cfg["seed"], "readout")
    s = np.stack([signal(rr, TRAIN_STEPS) for _ in range(max(i, o))], -1)
    u, y = s[:-1, :i], s[1:, :o]
    states = rollout_np(cfg, q, scale, w_in, u)[WASHOUT:]
    y = y[WASHOUT:].astype(np.float64)
    w_out = np.linalg.solve(states.T @ states + RIDGE * np.eye(r),
                            states.T @ y)
    return Weights(dense=dense, w_in=w_in, w_out=w_out.astype(np.float32),
                   q=q, scale=scale)


def reference_preds(cfg: dict, weights: Weights, inputs: list, *,
                    state_bits: int | None = None,
                    weight_bits: int | None = None,
                    float_dots: str = "highest") -> list:
    """Predictions (T_i, O) for each (T_i, I) input, from a zero state.

    Rolled as one batch per ``REF_ROWS`` requests, zero-padded to the
    block's longest (the recurrence is causal, so padding never reaches a
    request's own steps).  The keyword arguments compute the same thing in
    a lower precision, for the control: ``state_bits`` / ``weight_bits``
    below the configuration's, or ``float_dots="bfloat16"`` for the input
    projection and readout on bfloat16 operands.
    """
    import jax
    import jax.numpy as jnp

    sbits = cfg["state_bits"] if state_bits is None else state_bits
    if weight_bits is None:
        q, scale = weights.q, weights.scale
    else:
        q, scale = quantize(weights.dense, weight_bits)
    smax = (1 << (sbits - 1)) - 1
    leak = float(cfg["leak"])
    bf16 = float_dots == "bfloat16"
    hi = jax.lax.Precision.HIGHEST

    def proj(u, w_in):
        if bf16:
            return jnp.dot(u.astype(jnp.bfloat16), w_in.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        if u.shape[-1] == 1:        # a one-deep product: one exact multiply
            return u * w_in[0]
        return jnp.dot(u, w_in, precision=hi)

    def readout(x, w_out):
        if bf16:
            return jnp.dot(x.astype(jnp.bfloat16), w_out.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        return jnp.dot(x, w_out, precision=hi)

    @jax.jit
    def roll(u_tb, q, w_in, w_out):
        recur_scale = jnp.float32(scale / smax)

        def body(x, u):
            xq = jnp.clip(jnp.round(x * smax), -smax - 1, smax)
            ri = jnp.matmul(xq.astype(jnp.int8), q,
                            preferred_element_type=jnp.int32)
            pre = proj(u, w_in) + ri.astype(jnp.float32) * recur_scale
            x = (1.0 - leak) * x + leak * jnp.tanh(pre)
            return x, readout(x, w_out)

        x0 = jnp.zeros((u_tb.shape[1], q.shape[0]), jnp.float32)
        return jax.lax.scan(body, x0, u_tb)[1]

    qd = jnp.asarray(q, jnp.int8)
    w_in = jnp.asarray(weights.w_in)
    w_out = jnp.asarray(weights.w_out)
    out = []
    for lo in range(0, len(inputs), REF_ROWS):
        block = inputs[lo:lo + REF_ROWS]
        t_max = max(u.shape[0] for u in block)
        # pad the block to a fixed row count and a power-of-two length, so
        # few shapes compile
        t_pad = 1 << (t_max - 1).bit_length()
        u_tb = np.zeros((t_pad, REF_ROWS, block[0].shape[1]), np.float32)
        for j, u in enumerate(block):
            u_tb[:u.shape[0], j] = u
        y = np.asarray(roll(jnp.asarray(u_tb), qd, w_in, w_out))
        out += [y[:u.shape[0], j] for j, u in enumerate(block)]
    return out
