"""Plan-specialized Pallas rollout: resident weights, band pipeline, tiles.

The generic banded kernel (:mod:`.reservoir_rollout`) streams every band of
weight tiles from HBM on every one of the T grid steps — the roofline's
"weights re-read every token".  This kernel consumes a
:class:`repro.plan.RolloutProgram` instead and executes whichever regime
the plan selected:

* **resident** — all kept (folded) tiles fit the VMEM budget, so the
  weight operand uses a *constant* index map: Pallas fetches the block
  once and every later grid step reuses the on-chip copy — zero per-step
  weight traffic, the software analogue of the paper's spatially-resident
  matrix.  Grid: ``(T, B_tiles)``.
* **pipelined** — tiles exceed the budget; output columns are packed into
  bands of at most *half* the budget and the band axis streams.
  Pallas's pipeline emitter double-buffers the streamed operand: band
  ``k+1``'s DMA is issued while band ``k`` reduces.
  Grid: ``(T, n_bands, B_tiles)`` — the band axis sits OUTSIDE the batch
  tiles, so each band's tiles are fetched once per step and stay
  resident across the whole batch-tile sweep (band-inside-tiles would
  re-stream every band once per tile, multiplying exactly the HBM
  traffic this regime exists to bound).

Both regimes tile the batch axis: each grid step works on one
``b_tile``-row slice of the state, so a batch-64 rollout no longer runs
its compute as one monolithic VMEM block (the state carry itself is a
(B, R) scratch either way; in the resident regime the next-state scratch
shrinks to one tile).

The schedule's terms are the program's constant-propagated lowering:
``MM`` terms multiply a *folded* tile (int8 planes collapsed into the
quantized block — one int32 MXU pass instead of ``width`` shifted plane
passes) and ``SA`` terms unroll a sparse plane's few set digits as static
shift-adds.  int8 terms accumulate in exact int32, so any schedule is
bit-identical to the generic kernel; fp32 terms keep its ascending-row
order for the same guarantee.  The MXU takes the int8 state and int8
tiles as they are, accumulating in int32; shift-add digits are added in
place into their output lanes of an int32 scratch (Mosaic has no
scatter).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.sparse import FixedMatrix
from repro.kernels.platform import pallas_interpret
from repro.kernels.reservoir_rollout.reservoir_rollout import input_projection
from repro.plan import (DEFAULT_BATCH_TILE, DEFAULT_VMEM_BUDGET,
                        ExecutionPlan, plan_for, specialize_rollout)
from repro.plan.plan import pad_axis
from repro.plan.specialize import MM, SA


def _shift_add(acc_ref, xi, base, digits):
    """Add one SA term's digits into the (b, block) int32 ``acc_ref``.

    Each output lane ``j`` sums its digits' ``±(x[:, base + i] << w)`` into
    one (b, 1) column and adds it to that lane in place — the integers the
    scatter ``acc[:, j] += ...`` adds, without a scatter (Mosaic has none),
    in an order that cannot change them (int32 addition is exact).
    """
    by_lane: dict = {}
    for i, j, s, w in digits:
        by_lane.setdefault(j, []).append((i, s, w))
    for j, srcs in sorted(by_lane.items()):
        col = None
        for i, s, w in srcs:
            c = xi[:, base + i:base + i + 1] << w
            c = c if s > 0 else -c
            col = c if col is None else col + c
        acc_ref[:, j:j + 1] += col


def _specialized_kernel(*refs, schedules, leak, block, mode, smax,
                        recur_scale, n_bands, b_tile, n_steps, readout_every,
                        want_states, want_preds, want_final):
    if want_preds:
        u_ref, w_ref, win_ref, wout_ref, x0_ref, *rest = refs
    else:
        u_ref, w_ref, win_ref, x0_ref, *rest = refs
        wout_ref = None
    o_ref = rest.pop(0) if want_states else None
    y_ref = rest.pop(0) if want_preds else None
    f_ref = rest.pop(0) if want_final else None
    x_ref, nx_ref, *sa_ref = rest        # + int32 SA accumulator if any

    t = pl.program_id(0)
    if n_bands > 1:
        # pipelined grid (T, n_bands, B_tiles): the band axis is OUTSIDE
        # the batch tiles so each band's weights stream once per step
        k, bt = pl.program_id(1), pl.program_id(2)
    else:
        # resident grid (T, B_tiles): the weight block index never
        # changes, so the tiles were fetched exactly once
        k, bt = None, pl.program_id(1)
    bsl = pl.ds(bt * b_tile, b_tile)
    # next-state scratch: one batch tile suffices in the resident regime
    # (reduce + commit happen in the same grid step); the pipelined
    # regime interleaves batch tiles between a tile's bands, so every
    # tile's partials must stay live
    nsl = bsl if k is not None else pl.ds(0, b_tile)

    first_visit = (t == 0) if k is None else ((t == 0) & (k == 0))

    @pl.when(first_visit)
    def _seed_state():
        # each batch tile seeds its own state slice on its first visit
        x_ref[bsl, :] = x0_ref[...]

    x = x_ref[bsl, :]
    u = u_ref[0]
    if mode == "int8":
        # per-step state requantization, exactly as the generic kernel;
        # the engine admits state_bits <= 8 only, so the state is int8
        xq = jnp.clip(jnp.round(x * smax), -smax - 1, smax).astype(jnp.int8)
        if sa_ref:
            xi = xq.astype(jnp.int32)
    # int8 mode: the readout runs at HIGHEST (Mosaic's default rounds f32
    # operands to bfloat16); fp32 mode keeps the default (ROADMAP 1.4)
    prec = jax.lax.Precision.HIGHEST if mode == "int8" else None

    def run_band(cols):
        for ci, terms in cols:
            sl = slice(ci * block, (ci + 1) * block)
            if mode == "fp32":
                # ascending-row matmul order matches the generic kernel
                acc = None
                for _tag, slot, _shift, ri in terms:
                    xs = x[:, ri * block:(ri + 1) * block]
                    contrib = xs @ w_ref[0, slot]
                    acc = contrib if acc is None else acc + contrib
                pre = u @ win_ref[:, sl]
                if acc is not None:
                    pre = pre + acc
            else:
                # exact int32 accumulation: folded tiles + shift-add digits
                acc = jnp.zeros((b_tile, block), jnp.int32)
                for _tag, slot, shift, ri in (
                        term for term in terms if term[0] == MM):
                    xs = xq[:, ri * block:(ri + 1) * block]
                    acc = acc + (jax.lax.dot(
                        xs, w_ref[0, slot],
                        preferred_element_type=jnp.int32) << shift)
                sa_terms = [term for term in terms if term[0] == SA]
                if sa_terms:
                    sa_ref[0][...] = acc
                    for _tag, ri, digits in sa_terms:
                        _shift_add(sa_ref[0], xi, ri * block, digits)
                    acc = sa_ref[0][...]
                recur = acc.astype(jnp.float32) * recur_scale
                pre = input_projection(u, win_ref[:, sl]) + recur
            nx_ref[nsl, sl] = (1.0 - leak) * x[:, sl] + leak * jnp.tanh(pre)

    def commit():
        nx = nx_ref[nsl, :]
        x_ref[bsl, :] = nx
        if want_states:
            o_ref[0] = nx
        if want_final:
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

    if k is None:
        run_band(schedules[0])
        commit()
    else:
        for bi_, cols in enumerate(schedules):
            @pl.when(k == bi_)
            def _run_band(cols=cols):
                run_band(cols)

        @pl.when(k == n_bands - 1)
        def _commit_step():
            commit()


def specialized_rollout(
    u_seq: jnp.ndarray,
    w_data: jnp.ndarray,
    w_in: jnp.ndarray,
    x0: jnp.ndarray,
    w_out: jnp.ndarray | None = None,
    *,
    schedules: tuple,
    leak: float = 1.0,
    block: int = 128,
    mode: str = "fp32",
    smax: int = 127,
    recur_scale: float = 1.0,
    b_tile: int | None = None,
    readout_every: int = 1,
    want_states: bool = True,
    want_preds: bool = False,
    want_final: bool = False,
):
    """Launch one program-specialized rollout (see module docstring).

    ``u_seq`` is (T, B_pad, I) with ``B_pad`` already padded to a multiple
    of ``b_tile`` (the :class:`SpecializedRollout` wrapper handles this).
    Outputs mirror :func:`..reservoir_rollout.reservoir_rollout`: states /
    preds / final state in that order, bare when only one is requested.
    """
    t, b_pad, i = u_seq.shape
    r = x0.shape[1]
    n_bands, max_terms = w_data.shape[:2]
    b_tile = b_pad if b_tile is None else b_tile
    assert b_pad % b_tile == 0, (b_pad, b_tile)
    n_btiles = b_pad // b_tile
    assert r % block == 0 and w_in.shape == (i, r), (u_seq.shape, w_in.shape)
    assert len(schedules) == n_bands
    assert want_states or want_preds or want_final
    if want_preds:
        assert w_out is not None and w_out.shape[0] == r, w_out
        assert t % readout_every == 0, (t, readout_every)
        o = w_out.shape[1]

    kernel = functools.partial(
        _specialized_kernel, schedules=schedules, leak=leak, block=block,
        mode=mode, smax=smax, recur_scale=recur_scale, n_bands=n_bands,
        b_tile=b_tile, n_steps=t, readout_every=readout_every,
        want_states=want_states, want_preds=want_preds,
        want_final=want_final)

    # pipelined: bands OUTSIDE batch tiles (see kernel docstring)
    grid = (t, n_btiles) if n_bands == 1 else (t, n_bands, n_btiles)

    def im(f):
        """Arity-matched index map over the logical (ti, bi, ki) ids."""
        if n_bands == 1:
            return lambda ti, bi: f(ti, bi, 0)
        return lambda ti, ki, bi: f(ti, bi, ki)

    in_specs = [
        pl.BlockSpec((1, b_tile, i),
                     im(lambda ti, bi, ki: (ti, bi, 0))),       # u(t) tile
        # resident: ki is constant 0 -> the tiles are fetched exactly once;
        # pipelined: the band axis streams (and double-buffers) the tiles
        pl.BlockSpec((1, max_terms, block, block),
                     im(lambda ti, bi, ki: (ki, 0, 0, 0))),
        pl.BlockSpec((i, r), im(lambda ti, bi, ki: (0, 0))),    # w_in
    ]
    operands = [u_seq, w_data, w_in]
    if want_preds:
        in_specs.append(pl.BlockSpec((r, o), im(lambda ti, bi, ki: (0, 0))))
        operands.append(w_out)
    in_specs.append(pl.BlockSpec((b_tile, r),
                                 im(lambda ti, bi, ki: (bi, 0))))  # x0 tile
    operands.append(x0)

    out_shapes, out_specs = [], []
    if want_states:
        out_shapes.append(jax.ShapeDtypeStruct((t, b_pad, r), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, b_tile, r), im(lambda ti, bi, ki: (ti, bi, 0))))
    if want_preds:
        out_shapes.append(jax.ShapeDtypeStruct(
            (t // readout_every, b_pad, o), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (1, b_tile, o),
            im(lambda ti, bi, ki, _k=readout_every: (ti // _k, bi, 0))))
    if want_final:
        out_shapes.append(jax.ShapeDtypeStruct((b_pad, r), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (b_tile, r), im(lambda ti, bi, ki: (bi, 0))))

    scratch = [pltpu.VMEM((b_pad, r), jnp.float32),              # state
               # next state: one tile suffices when reduce and commit
               # share a grid step (resident regime)
               pltpu.VMEM((b_tile if n_bands == 1 else b_pad, r),
                          jnp.float32)]
    if any(term[0] == SA for cols in schedules for _ci, terms in cols
           for term in terms):
        # one column block's int32 sum, where shift-add digits land
        scratch.append(pltpu.VMEM((b_tile, block), jnp.int32))
    single = len(out_shapes) == 1
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes[0] if single else tuple(out_shapes),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs[0] if single else tuple(out_specs),
        scratch_shapes=scratch,
        interpret=pallas_interpret(),
    )(*operands)


class SpecializedRollout:
    """Program-driven fused rollout for one frozen reservoir.

    Drop-in for :class:`..ops.FusedRollout` with the plan-specialized
    lowering behind it: the regime (resident / pipelined), batch tiling
    and the folded/shift-add schedule all come from
    :func:`repro.plan.specialize_rollout`.  Each instance jits its own
    launch so it can offer a state-donating variant for the zero-copy
    chunk API and count its traces (``trace_counts``) for recompilation
    guards.
    """

    def __init__(self, source: FixedMatrix | ExecutionPlan, w_in, *,
                 leak: float = 1.0, mode: str = "fp32", state_bits: int = 8,
                 w_out=None,
                 vmem_budget: int | None = DEFAULT_VMEM_BUDGET,
                 readout_every: int = 1,
                 batch_tile_max: int = DEFAULT_BATCH_TILE,
                 crossover: int | None = None):
        plan = source if isinstance(source, ExecutionPlan) else plan_for(source)
        assert plan.shape[0] == plan.shape[1], "reservoir matrix must be square"
        assert mode in ("fp32", "int8"), mode
        assert plan.nbr == plan.nbc
        self.plan = plan
        self.program = specialize_rollout(
            plan, mode, vmem_budget=vmem_budget, crossover=crossover,
            batch_tile_max=batch_tile_max)
        self.dim = plan.shape[0]
        self.block = plan.block
        self.rpad = plan.cols_pad
        self.leak = float(leak)
        self.mode = mode
        self.readout_every = int(readout_every)
        self.smax = (1 << (state_bits - 1)) - 1
        self.recur_scale = plan.scale / self.smax
        self.w_in = jnp.asarray(
            pad_axis(np.asarray(w_in, np.float32), 1, self.rpad))
        self.w_out = None
        self.out_dim = 0
        if w_out is not None:
            wo = np.asarray(w_out, np.float32)
            assert wo.shape[0] == self.dim, wo.shape
            self.out_dim = wo.shape[1]
            opad = -(-self.out_dim // 128) * 128
            self.w_out = jnp.asarray(
                pad_axis(pad_axis(wo, 0, self.rpad), 1, opad))
        self.trace_counts: collections.Counter = collections.Counter()
        self._fns: dict = {}

    @property
    def regime(self) -> str:
        return self.program.regime

    @property
    def n_bands(self) -> int:
        return self.program.n_bands

    def _fn(self, donate: bool):
        fn = self._fns.get(donate)
        if fn is None:
            program, me = self.program, self

            def launch(u_seq, x0, *, want_states, want_preds,
                       want_final, b_tile):
                # trace-time side effect: one tick per compiled program
                # (donate is part of the key — a donated variant is a
                # distinct program, not a recompile)
                tkey = (u_seq.shape, want_states, want_preds,
                        want_final, donate, program.regime)
                me.trace_counts[tkey] += 1
                n = me.trace_counts[tkey]
                obs.event("pallas_trace" if n == 1 else "retrace",
                          backend="pallas", shape=str(u_seq.shape),
                          regime=program.regime, count=n)
                obs.inc("retrace_total" if n > 1
                        else "compile_traces_total", backend="pallas")
                # batch/lane padding AND output trimming live inside the
                # jit: the caller's (B, dim) carried-state buffer is the
                # donated argument itself, and the trimmed (B, dim) final
                # state can reuse it — pre-padding outside would donate a
                # throwaway temporary instead.
                t, b, _i = u_seq.shape
                b_pad = b_tile * (-(-b // b_tile))
                x0 = jnp.pad(x0.astype(jnp.float32),
                             ((0, b_pad - x0.shape[0]),
                              (0, me.rpad - x0.shape[1])))
                if b_pad != b:
                    u_seq = jnp.pad(u_seq, ((0, 0), (0, b_pad - b), (0, 0)))
                out = specialized_rollout(
                    u_seq.astype(jnp.float32), program.data, me.w_in, x0,
                    me.w_out if want_preds else None,
                    schedules=program.schedules, leak=me.leak,
                    block=me.block, mode=me.mode, smax=me.smax,
                    recur_scale=me.recur_scale, b_tile=b_tile,
                    readout_every=me.readout_every,
                    want_states=want_states, want_preds=want_preds,
                    want_final=want_final)
                parts = list(out) if isinstance(out, tuple) else [out]
                trimmed = []
                if want_states:
                    trimmed.append(parts.pop(0)[:, :b, : me.dim])
                if want_preds:
                    trimmed.append(parts.pop(0)[:, :b, : me.out_dim])
                if want_final:
                    trimmed.append(parts.pop(0)[:b, : me.dim])
                return trimmed[0] if len(trimmed) == 1 else tuple(trimmed)

            fn = jax.jit(
                launch,
                static_argnames=("want_states", "want_preds",
                                 "want_final", "b_tile"),
                donate_argnums=(1,) if donate else ())
            self._fns[donate] = fn
        return fn

    def __call__(self, u_seq: jnp.ndarray, x0: jnp.ndarray | None = None, *,
                 want_states: bool = True, want_preds: bool = False,
                 want_final: bool = False, donate_state: bool = False):
        """u_seq: (T, B, I) -> the requested outputs (states, preds, final
        state), exactly as :class:`..ops.FusedRollout`.  ``donate_state``
        donates ``x0`` to the launch so the emitted final state can reuse
        its buffer (the chunked scheduler's carried slot states)."""
        assert want_states or want_preds or want_final
        assert not want_preds or self.w_out is not None, \
            "fused readout requested but no w_out attached"
        _t, b, _ = u_seq.shape
        b_tile, _n_tiles, _b_pad = self.program.batch_tiling(b)
        if x0 is None:
            x0 = jnp.zeros((b, self.dim), jnp.float32)
        return self._fn(donate_state)(
            u_seq, jnp.asarray(x0), want_states=want_states,
            want_preds=want_preds, want_final=want_final,
            b_tile=b_tile)
