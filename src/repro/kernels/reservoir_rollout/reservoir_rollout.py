"""Pallas TPU kernel: fused batched reservoir rollout (banded, fused readout).

T steps of paper Eq. 1 for a whole state batch in ONE kernel launch:

    x(n) = (1 - leak) * x(n-1) + leak * f(u(n) @ W_in + x(n-1) @ W)
    y(n) = x(n) @ W_out                                       (optional, Eq. 2)

The grid is ``(T, n_bands)`` — TPU grids execute sequentially, so VMEM
scratch carries the state batch across steps while each inner grid step
streams exactly ONE band's weight tiles into VMEM.  Bands come from the
:class:`repro.plan.ExecutionPlan` lowering: output column blocks are packed
into bands whose tiles fit the VMEM budget, which is what lets dim-2048
fp32 rollouts compile instead of overflowing scratch.  With one band this
degenerates to the original whole-matrix-resident kernel.

The reduction is driven by the plan's *static* per-band term lists — the
Python loops unroll at trace time, so culled blocks (and, in int8 mode,
culled digit plane-blocks) cost nothing: the MXU analogue of the paper's
synthesis-time adder culling.  Two modes share one kernel body:

* ``fp32``  — dequantized tiles, bit-compatible with
  ``BlockSparse.matmul_ref`` accumulation order (shift is 0 and unused).
* ``int8``  — exact digit-plane arithmetic: the state batch is requantized
  to int8 every step and each term is a shifted int8 x int8 plane-tile
  dot accumulated in int32.

The optional fused readout applies ``W_out`` to the new state inside the
launch (at every step, or every ``readout_every`` steps), so serving can
return predictions without ever materializing the state trajectory in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_interpret


def input_projection(u, w_in):
    """``u @ w_in`` for an int8-mode kernel, whose recurrence is exact.

    A pre-activation one ulp off the reference's can flip a requantized
    state digit, which the recurrence carries on and the readout
    multiplies.  A one-deep product is therefore one f32 multiply on the
    VPU, as exact as the reference's; the MXU, even at HIGHEST, rounds
    differently.  Deeper products run on the MXU at HIGHEST.
    """
    if u.shape[1] == 1:
        return u * w_in
    return jnp.dot(u, w_in, precision=jax.lax.Precision.HIGHEST)


def _rollout_kernel(*refs, band_plans, leak, block, mode, smax, recur_scale,
                    n_bands, n_steps, readout_every, want_states, want_preds,
                    want_final):
    if want_preds:
        u_ref, w_ref, win_ref, wout_ref, x0_ref, *rest = refs
    else:
        u_ref, w_ref, win_ref, x0_ref, *rest = refs
        wout_ref = None
    o_ref = rest.pop(0) if want_states else None
    y_ref = rest.pop(0) if want_preds else None
    f_ref = rest.pop(0) if want_final else None
    x_ref, nx_ref = rest

    t = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when((t == 0) & (k == 0))
    def _load_initial_state():
        x_ref[...] = x0_ref[...]

    x = x_ref[...]
    u = u_ref[0]
    if mode == "int8":
        # Per-step state requantization, exactly as esn._step_int8 does it
        # (state_bits <= 8, so the quantized state is int8).
        xq = jnp.clip(jnp.round(x * smax), -smax - 1, smax).astype(jnp.int8)
    # int8 mode: the readout runs at HIGHEST (Mosaic's default rounds f32
    # operands to bfloat16); fp32 mode keeps the default (ROADMAP 1.4)
    prec = jax.lax.Precision.HIGHEST if mode == "int8" else None
    b = x.shape[0]

    for bi, cols in enumerate(band_plans):
        @pl.when(k == bi)
        def _run_band(cols=cols):
            # w_ref holds band bi's tiles exactly when k == bi (BlockSpec).
            for ci, terms in cols:
                sl = slice(ci * block, (ci + 1) * block)
                if mode == "fp32":
                    acc = None
                    for slot, _shift, ri in terms:
                        xs = x[:, ri * block:(ri + 1) * block]
                        contrib = xs @ w_ref[0, slot]
                        acc = contrib if acc is None else acc + contrib
                    pre = u @ win_ref[:, sl]
                    if acc is not None:
                        pre = pre + acc
                else:
                    acc = jnp.zeros((b, block), jnp.int32)
                    for slot, shift, ri in terms:
                        xs = xq[:, ri * block:(ri + 1) * block]
                        acc = acc + (jax.lax.dot(
                            xs, w_ref[0, slot],
                            preferred_element_type=jnp.int32) << shift)
                    recur = acc.astype(jnp.float32) * recur_scale
                    pre = input_projection(u, win_ref[:, sl]) + recur
                nx_ref[:, sl] = (1.0 - leak) * x[:, sl] + leak * jnp.tanh(pre)

    @pl.when(k == n_bands - 1)
    def _commit_step():
        nx = nx_ref[...]
        x_ref[...] = nx
        if want_states:
            o_ref[0] = nx
        if want_final:
            # The chunked-serving carry: x(T) leaves the launch as its own
            # (B, R) output so a later chunk can resume bit-identically.
            @pl.when(t == n_steps - 1)
            def _emit_final_state():
                f_ref[...] = nx
        if want_preds:
            if readout_every == 1:
                y_ref[0] = jnp.dot(nx, wout_ref[...], precision=prec)
            else:
                @pl.when((t + 1) % readout_every == 0)
                def _emit_readout():
                    y_ref[0] = jnp.dot(nx, wout_ref[...], precision=prec)


@functools.partial(jax.jit, static_argnames=(
    "band_plans", "leak", "block", "mode", "smax", "recur_scale",
    "readout_every", "want_states", "want_preds", "want_final"))
def reservoir_rollout(
    u_seq: jnp.ndarray,
    w_data: jnp.ndarray,
    w_in: jnp.ndarray,
    x0: jnp.ndarray,
    w_out: jnp.ndarray | None = None,
    *,
    band_plans: tuple,
    leak: float = 1.0,
    block: int = 128,
    mode: str = "fp32",
    smax: int = 127,
    recur_scale: float = 1.0,
    readout_every: int = 1,
    want_states: bool = True,
    want_preds: bool = False,
    want_final: bool = False,
):
    """Fused T-step rollout for a state batch, optionally banded + readout.

    Args:
        u_seq: (T, B, I) inputs, float32.
        w_data: (n_bands, max_terms, block, block) banded weight tiles from
            ``ExecutionPlan.rollout_layout`` — float32 dequantized tiles
            (fp32 mode) or int8 digit-plane tiles (int8 mode).
        w_in: (I, R) input weights, R padded to a multiple of ``block``.
        x0: (B, R) initial states.
        w_out: (R, O) readout weights (required iff ``want_preds``), O
            padded to a lane multiple.
        band_plans: static nested tuple, one entry per band; each entry
            lists ``(ci, ((slot, shift, row_block), ...))`` per output
            column block.  Culled blocks/plane-blocks never appear.
        leak: leak rate of Eq. 1.
        mode: "fp32" or "int8".
        smax / recur_scale: int8-mode state quantization range and the
            ``scale / smax`` factor restoring float pre-activations.
        readout_every: emit predictions every k steps (k must divide T).
        want_states / want_preds: which outputs to materialize; dropping
            states keeps the trajectory entirely in VMEM.
        want_final: additionally emit x(T), the post-rollout state batch
            (B, R) — the carry the chunked scheduler resumes from.

    Returns:
        The requested outputs in the order states (T, B, R),
        preds (T // readout_every, B, O), final state (B, R) — a bare
        array when exactly one of ``want_states`` / ``want_preds`` /
        ``want_final`` is set, else a tuple.
    """
    t, b, i = u_seq.shape
    r = x0.shape[1]
    n_bands, max_terms = w_data.shape[:2]
    assert r % block == 0 and w_in.shape == (i, r), (u_seq.shape, w_in.shape)
    assert len(band_plans) == n_bands
    assert want_states or want_preds or want_final
    if want_preds:
        assert w_out is not None and w_out.shape[0] == r, w_out
        assert t % readout_every == 0, (t, readout_every)
        o = w_out.shape[1]

    kernel = functools.partial(
        _rollout_kernel, band_plans=band_plans, leak=leak, block=block,
        mode=mode, smax=smax, recur_scale=recur_scale, n_bands=n_bands,
        n_steps=t, readout_every=readout_every, want_states=want_states,
        want_preds=want_preds, want_final=want_final)

    in_specs = [
        pl.BlockSpec((1, b, i), lambda ti, ki: (ti, 0, 0)),        # u(t)
        pl.BlockSpec((1, max_terms, block, block),
                     lambda ti, ki: (ki, 0, 0, 0)),                # band tiles
        pl.BlockSpec((i, r), lambda ti, ki: (0, 0)),               # w_in
    ]
    operands = [u_seq, w_data, w_in]
    if want_preds:
        in_specs.append(pl.BlockSpec((r, o), lambda ti, ki: (0, 0)))
        operands.append(w_out)
    in_specs.append(pl.BlockSpec((b, r), lambda ti, ki: (0, 0)))   # x0
    operands.append(x0)

    out_shapes, out_specs = [], []
    if want_states:
        out_shapes.append(jax.ShapeDtypeStruct((t, b, r), jnp.float32))
        out_specs.append(pl.BlockSpec((1, b, r), lambda ti, ki: (ti, 0, 0)))
    if want_preds:
        out_shapes.append(jax.ShapeDtypeStruct(
            (t // readout_every, b, o), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, b, o),
            lambda ti, ki, _k=readout_every: (ti // _k, 0, 0)))
    if want_final:
        out_shapes.append(jax.ShapeDtypeStruct((b, r), jnp.float32))
        out_specs.append(pl.BlockSpec((b, r), lambda ti, ki: (0, 0)))

    single = len(out_shapes) == 1
    out = pl.pallas_call(
        kernel,
        out_shape=out_shapes[0] if single else tuple(out_shapes),
        grid=(t, n_bands),
        in_specs=in_specs,
        out_specs=out_specs[0] if single else tuple(out_specs),
        scratch_shapes=[pltpu.VMEM((b, r), jnp.float32),           # state
                        pltpu.VMEM((b, r), jnp.float32)],          # next state
        interpret=pallas_interpret(),
    )(*operands)
    return out
