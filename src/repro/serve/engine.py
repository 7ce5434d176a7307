"""Batched reservoir-rollout engine — the serving face of the paper.

The paper's win is specializing the *recurrent* multiply of a frozen
reservoir; serving-side, the unit of work is therefore the whole rollout
``x(n) = f(W_in u(n) + W x(n-1))`` over a request batch, not a single gemv.
Every backend builds from the one shared :class:`repro.plan.ExecutionPlan`
lowering of the reservoir matrix (the TPU analogue of the paper's
compile-to-bitstream step) and fronts two fused implementations:

* ``xla``    — a jitted ``lax.scan`` whose body does the *batched*
  recurrent multiply natively (dense or block-culled, dispatched on the
  plan's structure) with the input projection hoisted into a single
  (B*T, I) x (I, R) gemm before the scan.
* ``pallas`` — the ``reservoir_rollout`` Pallas kernel fed by the plan's
  VMEM-banded layout: T steps fused in one launch, state resident in VMEM,
  one band of weight tiles streamed per grid step.  Compiled on the TPU,
  run by the Pallas interpreter on the CPU — the platform decides
  (:mod:`repro.kernels.platform`), never a caller option.

``backend="auto"`` takes the backend :func:`repro.plan.choose_schedule`
picks from the plan and the platform: Pallas on the TPU where the folded
tiles fit VMEM, else XLA.

With a trained readout the engine serves *predictions*: ``W_out`` is
applied inside the one compiled rollout, so only predictions leave the
device.  The Pallas kernel applies it per step inside the launch and
never materializes the state trajectory; XLA's scan stacks the (B, T, R)
trajectory and applies ``W_out`` after the scan.  The request/response
surface is the unified
:class:`~repro.serve.api.SubmitSpec` -> :class:`~repro.serve.api.RolloutResult`
contract (``submit`` / ``submit_many``); ``want_states=True`` on the spec
keeps the states contract, and the chunked schedulers drive
:meth:`ReservoirEngine.run_segment` directly.
"""

from __future__ import annotations

import collections
import functools
import time
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.esn import ESNParams
from repro.kernels.reservoir_rollout.ops import FusedRollout
from repro.kernels.reservoir_rollout.specialized import SpecializedRollout
from repro.plan import (DEFAULT_BATCH_TILE, DEFAULT_VMEM_BUDGET,
                        choose_schedule, plan_for, specialize_rollout)
from repro.plan.specialize import int8_recur_reference, specialize_summary
from repro.serve.api import (_UNSET, RolloutResult, SubmitSpec,
                             lifecycle_timings, warn_deprecated)
from repro.serve.batching import MicroBatch, PaddingBucketer, RolloutRequest
from repro.serve.stats import ServeStats

# Buffer donation is a no-op on the CPU backend; jax warns about it on
# every donated dispatch, which would swamp the zero-copy serve loop's
# output.  The filter wraps OUR donated dispatches only — never globally,
# so user code's own donation warnings still surface.
_DONATION_WARNING = "Some donated buffers were not usable"

# one process-wide warning for deadline-bearing specs on the one-shot
# path (the result still records timings["deadline_ignored"] every time)
_WARNED_DEADLINE = False


def _call_span(deferred: bool) -> str:
    """The span of one engine call: deferred calls time the dispatch only,
    synced ones the device wait too, so the two never share a name."""
    return "engine.dispatch" if deferred else "engine.rollout"


def donated_call(fn, u, x0b):
    """Invoke a donated rollout with the no-op-donation warning muted
    (shared by the single-device and sharded dispatch paths)."""
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=_DONATION_WARNING)
        return fn(u, x0b)

# Below this nonzero-block density the culled block loop beats one dense
# (B, R) x (R, R) product; above it the MXU/gemm wins.  Reservoirs at the
# paper's element sparsities (0.75-0.9) have dense *block* structure at
# block 128, so they take the dense path; block-structured matrices (and
# the paper's 0.98+ regimes at small blocks) take the culled loop.
DENSE_DISPATCH_DENSITY = 0.5


class ReservoirEngine:
    """Fused batched rollout (and readout) for one frozen ESN."""

    def __init__(self, params: ESNParams, *, backend: str = "auto",
                 stats: ServeStats | None = None,
                 dense_dispatch_density: float = DENSE_DISPATCH_DENSITY,
                 vmem_budget: int | None = _UNSET,
                 specialize: bool = True, tenant: str | None = None,
                 crossover: int | None = None,
                 batch_tile_max: int | None = None):
        assert backend in ("auto", "xla", "pallas"), backend
        self.params = params
        self.config = params.config
        self.stats = stats if stats is not None else ServeStats()
        # registry model name this engine serves (None outside a
        # registry); threads through to the plan-cache tenant counters
        self.tenant = tenant
        self.plan = plan_for(params.w, tenant=tenant)
        self.specialize = specialize
        self._int8 = self.config.mode.startswith("int8")
        if self._int8 and self.config.state_bits > 8:
            # every int8 recurrence multiplies the requantized state as an
            # int8 operand; a wider state would be silently clipped
            raise ValueError(
                f"mode {self.config.mode!r} takes state_bits <= 8 (the "
                f"requantized state is an int8 operand), got "
                f"state_bits={self.config.state_bits}")
        # backend="auto" takes the schedule the plan and the platform
        # decide (repro.plan.schedule): it fills every knob the caller left
        # unset, and explicit kwargs win (a caller pinning the budget
        # keeps it).
        self.requested_backend = backend
        self.schedule = None
        if backend == "auto" and specialize:
            sched = self.schedule = choose_schedule(
                self.plan, "int8" if self._int8 else "fp32",
                jax.default_backend())
            backend = sched.backend
            if vmem_budget is _UNSET:
                vmem_budget = sched.vmem_budget
            if crossover is None:
                crossover = sched.crossover
            if batch_tile_max is None:
                batch_tile_max = sched.batch_tile_max
        self.backend = "xla" if backend == "auto" else backend
        self.vmem_budget = DEFAULT_VMEM_BUDGET if vmem_budget is _UNSET \
            else vmem_budget
        self.crossover = crossover
        self.batch_tile_max = batch_tile_max
        # Readout captured at construction; engine_for invalidates the
        # cached engine when params.w_out is replaced (fit_readout).
        self._w_out = params.w_out
        # plan.block_density (not plan.stats) keeps the fp32 path from
        # paying for the integer lowering just to make a dispatch decision
        self._dense_density = dense_dispatch_density
        self.uses_dense = (not self._int8 and
                           self.plan.block_density >= dense_dispatch_density)
        # specialized int8, by the program's structure: a block-dense
        # matrix whose program is all folded tiles takes one folded int32
        # gemm (the whole digit-plane fold); the rest run the program's
        # culled tiles plus its scattered table
        self._int8_dense = (self._int8 and specialize and
                            self._int8_summary()["kind"] == "tiles" and
                            self.plan.block_density >= dense_dispatch_density)
        # trace-time tick per compiled rollout: the recompilation guard
        # (N chunks must trace once per shape/regime, never per chunk)
        self._xla_traces: collections.Counter = collections.Counter()
        obs.event("engine_build", backend=self.backend, tenant=tenant,
                  schedule=str(self.schedule))
        obs.inc("engine_builds_total", backend=self.backend)
        if self.backend == "pallas":
            kw = {}
            if specialize:
                # the schedule knobs are a specialization concept; the
                # generic banded FusedRollout has no crossover/tiling
                kw = {"crossover": self.crossover,
                      "batch_tile_max": self.batch_tile_max
                      or DEFAULT_BATCH_TILE}
            cls = SpecializedRollout if specialize else FusedRollout
            self._fused = cls(
                self.plan, params.w_in, leak=self.config.leak,
                mode="int8" if self._int8 else "fp32",
                state_bits=self.config.state_bits,
                w_out=self._w_out, vmem_budget=self.vmem_budget, **kw)
        else:
            # jitted rollouts keyed on (with_readout, with_final, donated);
            # built lazily except the plain states path every caller hits
            # first.
            self._xla_fns = {
                (False, False, False): self._build_xla_fn(False, False)}

    def _xla(self, with_readout: bool, with_final: bool,
             donate: bool = False):
        key = (with_readout, with_final, donate)
        fn = self._xla_fns.get(key)
        if fn is None:
            fn = self._xla_fns[key] = self._build_xla_fn(
                with_readout, with_final, donate)
        return fn

    # -- fused XLA rollout ---------------------------------------------------
    def _build_xla_fn(self, with_readout: bool, with_final: bool,
                      donate: bool = False):
        params, cfg = self.params, self.config
        w, w_in = params.w, params.w_in
        int8 = self._int8
        leak = cfg.leak
        smax = (1 << (cfg.state_bits - 1)) - 1
        dim = cfg.reservoir_dim
        plan = self.plan
        w_out = jnp.asarray(self._w_out, jnp.float32) if with_readout else None
        traces = self._xla_traces
        # The engine may be constructed lazily inside someone else's jit
        # trace (run_reservoir under jax.jit); the dense closure constant
        # must be materialized eagerly or it leaks that trace.
        with jax.ensure_compile_time_eval():
            w_dense = w.dense_f32() if self.uses_dense else None
            # Specialized int8: constant-propagate the 2^w plane scales
            # and signs at build time.  Block-dense matrices fold ALL
            # planes into the quantized matrix — one int32 gemm replaces
            # the width shifted pos/neg plane products, bit-identically
            # (int32 accumulation is exact).  Block-sparse ones run the
            # program's culled folded tiles and scattered table.
            q_folded = w.q if self._int8_dense else None
            program = None
            if int8 and self.specialize and not self._int8_dense:
                program = specialize_rollout(
                    plan, "int8", vmem_budget=self.vmem_budget,
                    crossover=self.crossover,
                    batch_tile_max=self.batch_tile_max
                    or DEFAULT_BATCH_TILE)
        schedule = self.xla_schedule
        # int8: the float products run at full f32 precision (the TPU's
        # default rounds f32 operands to bfloat16, which the exact integer
        # recurrence would carry on); fp32 keeps the default (ROADMAP 1.4)
        prec = jax.lax.Precision.HIGHEST if int8 else None

        def rollout(u_bt: jnp.ndarray, x0: jnp.ndarray) -> jnp.ndarray:
            # trace-time side effect: the recompilation-guard counter
            # (donate is part of the key — the donated variant is a
            # legitimately distinct program, not a recompile)
            key = (u_bt.shape, with_readout, with_final, donate, schedule)
            traces[key] += 1
            n = traces[key]
            obs.event("xla_trace" if n == 1 else "retrace",
                      backend="xla", shape=str(u_bt.shape),
                      schedule=schedule, count=n)
            obs.inc("retrace_total" if n > 1 else "compile_traces_total",
                    backend="xla")
            # One gemm projects every input of every step before the scan.
            uproj = jnp.matmul(u_bt.astype(jnp.float32), w_in,
                               precision=prec)                # (B, T, R)
            uproj_t = jnp.swapaxes(uproj, 0, 1)              # (T, B, R)

            def body(x, up):
                if int8:
                    xq = jnp.clip(jnp.round(x * smax), -smax - 1,
                                  smax).astype(jnp.int8)
                    if q_folded is not None:
                        ri = jnp.matmul(xq, q_folded,
                                        preferred_element_type=jnp.int32)
                    elif program is not None:
                        ri = int8_recur_reference(
                            program, xq, plan.rows_pad, dim)
                    else:
                        ri = w.matvec_int_exact(xq)
                    recur = ri.astype(jnp.float32) * (w.scale / smax)
                elif w_dense is not None:
                    recur = x @ w_dense
                else:
                    recur = w.matmul(x)
                nxt = jnp.tanh(up + recur)
                nxt = (1.0 - leak) * x + leak * nxt
                return nxt, nxt

            xf, states = jax.lax.scan(body, x0, uproj_t)
            out = jnp.swapaxes(states, 0, 1)                 # (B, T, R)
            if with_readout:
                # Fused readout: W_out applied inside the same compiled
                # program — one dispatch, predictions only leave the device,
                # and the result is the exact predict(states) contraction.
                out = jnp.matmul(out, w_out, precision=prec)  # (B, T, O)
            if with_final:
                # xf is the scan carry — exactly x(T), so chunked rollouts
                # that resume from it reproduce the one-shot trajectory
                # bit for bit.
                return out, xf
            return out

        # Donating x0 lets XLA reuse the carried-state buffer for the
        # emitted final state — the zero-copy half of the chunk API.
        return jax.jit(rollout, donate_argnums=(1,) if donate else ())

    # -- backend dispatch ----------------------------------------------------
    @property
    def xla_schedule(self) -> str:
        """Which specialized XLA recurrence this engine compiled."""
        if not self._int8:
            return "fp32-dense" if self.uses_dense else "fp32-culled"
        if self._int8_dense:
            return "int8-folded-dense"
        if self.specialize:
            return "int8-folded-culled"
        return "int8-planes"

    @property
    def lowering(self) -> str:
        """What computes the recurrence: the XLA schedule, or ``pallas-``
        with the program's kind and regime (``generic`` for the banded
        kernel)."""
        if self.backend != "pallas":
            return self.xla_schedule
        prog = self.program
        if prog is None:
            return "pallas-generic"
        return f"pallas-{prog.kind}-{prog.regime}"

    @functools.cached_property
    def _recur_macs(self) -> int:
        """Multiply-adds (digit adds included) the recurrence issues per
        row and step, padding included: what the lowering computes, not
        what the matrix needs."""
        lowering, plan = self.lowering, self.plan
        dim = self.config.reservoir_dim
        tile = plan.block * plan.block
        if lowering in ("fp32-dense", "int8-folded-dense"):
            return dim * dim
        if lowering in ("fp32-culled", "pallas-generic") and not self._int8:
            return plan.blocks_nnz * tile
        if lowering == "pallas-generic":
            return plan.stats.int8_terms_kept * tile
        if lowering == "int8-planes":
            return 2 * plan.width * dim * dim      # pos and neg planes
        if lowering.startswith("pallas-"):
            prog = self.program
            return prog.n_matmul_terms * tile + prog.shiftadd_digits
        s = self._int8_summary()                   # culled tiles + table
        return s["n_matmul_terms"] * tile + s["table_slots"]

    def _int8_summary(self) -> dict:
        """The counts of this engine's int8 program (kind, tiles, table);
        none of them depends on the band budget."""
        return specialize_summary(self.plan, "int8", vmem_budget=None,
                                  crossover=self.crossover)

    @property
    def program(self):
        """The pallas backend's :class:`~repro.plan.RolloutProgram` (None
        on the XLA backend or with ``specialize=False``)."""
        return getattr(getattr(self, "_fused", None), "program", None)

    @property
    def trace_counts(self) -> collections.Counter:
        """Rollout traces per (shape, outputs, regime/schedule) — the
        recompilation guard: rolling N chunks of one shape must leave
        every count at exactly 1."""
        fused = getattr(self, "_fused", None)
        if fused is not None and hasattr(fused, "trace_counts"):
            return self._xla_traces + fused.trace_counts
        return collections.Counter(self._xla_traces)

    def _local_rollout(self, with_readout: bool, with_final: bool,
                       donate: bool = False):
        """The pure ``(B, T, I), (B, R) -> (B, T, *)`` rollout callable.

        Batch rows are independent through it (the recurrence never mixes
        rows), which is the property the sharded engine relies on: the same
        callable is the ``shard_map`` body in :mod:`repro.dist`, one
        replica per data shard over the batch axis.
        """
        if self.backend == "pallas":
            fused = self._fused
            kw = {"donate_state": donate} if isinstance(
                fused, SpecializedRollout) else {}

            def fn(u_bt, x0):
                out = fused(jnp.swapaxes(u_bt, 0, 1), x0,
                            want_states=not with_readout,
                            want_preds=with_readout,
                            want_final=with_final, **kw)
                y, xf = out if with_final else (out, None)
                y = jnp.swapaxes(y, 0, 1)
                return (y, xf) if with_final else y

            return fn
        return self._xla(with_readout, with_final, donate)

    def _dispatch(self, u, x0b, with_readout: bool, with_final: bool,
                  donate: bool = False):
        """One fused rollout call -> ``(out, final_state_or_None)``."""
        fn = self._local_rollout(with_readout, with_final, donate)
        out = donated_call(fn, u, x0b) if donate else fn(u, x0b)
        return out if with_final else (out, None)

    # -- public API ----------------------------------------------------------
    @property
    def has_readout(self) -> bool:
        """Whether a trained ``W_out`` is baked into this engine (serving
        defaults to predictions when True, states otherwise)."""
        return self._w_out is not None

    def _prepare(self, inputs, x0):
        u = jnp.asarray(inputs)
        single = u.ndim == 2
        if single:
            u = u[None]
        b = u.shape[0]
        dim = self.config.reservoir_dim
        if x0 is None:
            x0b = jnp.zeros((b, dim), jnp.float32)
        else:
            x0b = jnp.asarray(x0, jnp.float32)
            if x0b.ndim == 1:
                x0b = jnp.broadcast_to(x0b, (b, dim))
        return u, x0b, single

    def _executed_rows(self, batch: int) -> int:
        """Rows one rollout of ``batch`` sequences really runs: the
        specialized kernel pads the batch to whole batch tiles."""
        prog = self.program
        return batch if prog is None else prog.batch_tiling(batch)[2]

    def _record(self, out, batch, steps, t0, real_steps, defer=False):
        # Under an outer jit/vmap/grad trace the inputs are tracers: still
        # composable (the jitted fn nests), but timing/stats are meaningless
        # there — skip them instead of calling block_until_ready on a tracer.
        if not isinstance(out, jax.core.Tracer):
            if not defer:
                out.block_until_ready()
            # defer=True is the zero-copy serve loop: no host sync per
            # chunk — the recorded time is dispatch-side only (the
            # device->host wait lands at slot retirement), so the call is
            # flagged in the stats and throughput should be read from the
            # scheduler's makespan clock, not ServeStats.seconds.
            seconds = time.perf_counter() - t0
            # padded rows count as executed-but-padded work, so
            # padding_efficiency stays honest about batch-tile padding
            rows = self._executed_rows(batch)
            self.stats.record_call(
                batch=rows, steps=steps,
                seconds=seconds, deferred=defer,
                real_steps=batch * steps if real_steps is None
                else real_steps)
            obs.span(_call_span(defer), t0, t0 + seconds,
                     backend=self.backend, batch=batch, steps=steps,
                     deferred=defer,
                     recur_ops=self._recur_macs * rows * steps)
            obs.inc("rollout_launches_total", lowering=self.lowering)
        return out

    def _resolve_want(self, want_states: bool | None) -> bool:
        want = (not self.has_readout) if want_states is None \
            else bool(want_states)
        if not want and self._w_out is None:
            raise ValueError("readout not trained; call fit_readout first "
                             "(or submit with want_states=True)")
        return want

    def run_segment(self, inputs, x0, *, want_states: bool = False,
                    real_steps: int | None = None,
                    donate_state: bool = False,
                    defer_sync: bool = False):
        """The chunk-serving primitive: ``(B, T, I), (B, R) -> (out, x_end)``.

        One fused rollout of a batch segment from the carried states,
        ALWAYS returning the post-segment states — the carry the next
        segment resumes from bit-identically.  ``donate_state=True``
        donates the ``x0`` buffer to the launch (the caller must not reuse
        it; the chunked scheduler owns its carry) and ``defer_sync=True``
        skips the per-call host sync so the serve loop only waits for the
        device at slot retirement.  Strictly batched: no 2D single-sequence
        convenience — that is :meth:`submit`'s job.
        """
        if not want_states and self._w_out is None:
            raise ValueError("readout not trained; call fit_readout first "
                             "(or run the segment with want_states=True)")
        u = jnp.asarray(inputs)
        x0b = jnp.asarray(x0, jnp.float32)
        b, t = u.shape[0], u.shape[1]
        with obs.annotation(_call_span(defer_sync)):
            t0 = time.perf_counter()
            out, xf = self._dispatch(u, x0b, not want_states, True,
                                     donate_state)
            self._record(out, b, t, t0, real_steps, defer=defer_sync)
        return out, xf

    def submit(self, spec: SubmitSpec) -> RolloutResult:
        """One-shot serve of a single :class:`SubmitSpec`.

        ``inputs`` may be (T, I) or pre-batched (B, T, I); the result's
        ``preds``/``states``/``final_state`` match that leading shape.
        ``final_state`` is exactly x(T) — the chunk-resume carry.
        ``spec.deadline`` cannot be enforced here (no queue to wait in,
        and the fused rollout is not preemptible): a spec carrying one
        warns once per process and the result records
        ``timings["deadline_ignored"] = True`` so callers can tell the
        contract was not honored — deadline-bearing work belongs on a
        server.  Routed ``spec.model`` requests belong on a
        registry-backed server or :meth:`ModelRegistry.submit`.
        """
        if spec.model is not None:
            raise ValueError(
                f"spec routes to model {spec.model!r} but this is a bare "
                "single-model engine; submit through a registry-backed "
                "server (or ModelRegistry.submit)")
        deadline_ignored = spec.deadline is not None
        if deadline_ignored:
            global _WARNED_DEADLINE
            if not _WARNED_DEADLINE:
                _WARNED_DEADLINE = True
                warnings.warn(
                    "SubmitSpec.deadline is ignored by one-shot "
                    "ReservoirEngine.submit (there is no queue to wait "
                    "in); submit through AsyncReservoirServer to get "
                    "deadline enforcement", UserWarning, stacklevel=2)
        want = self._resolve_want(spec.want_states)
        u, x0b, single = self._prepare(spec.inputs, spec.x0)
        b, t, _ = u.shape
        trace_id = spec.trace_id or obs.new_trace_id()
        t0 = time.perf_counter()
        out, xf = self._dispatch(u, x0b, not want, True, False)
        self._record(out, b, t, t0, None)
        finish = time.perf_counter()
        obs.span("request.serve", t0, finish, trace_id=trace_id,
                 clock="wall", batch=b, steps=t)
        obs.observe("request_latency_seconds", finish - t0, path="engine")
        if single:
            out, xf = out[0], xf[0]
        timings = lifecycle_timings(arrival_time=t0, admit_time=t0,
                                    finish_time=finish,
                                    seconds=finish - t0,
                                    trace_id=trace_id)
        if deadline_ignored:
            timings["deadline_ignored"] = True
        return RolloutResult(preds=None if want else out,
                             states=out if want else None,
                             final_state=xf,
                             timings=timings)

    def submit_many(self, specs: Sequence[SubmitSpec],
                    bucketer: PaddingBucketer | None = None) -> dict:
        """Batch, pad and roll a set of variable-length specs.

        Returns ``{uid: RolloutResult}`` (specs without a ``uid`` get
        ``req<position>``).  Specs sharing a resolved ``want_states`` ride
        the same padded microbatches; padding overhead lands in
        ``self.stats``.  ``final_state`` is ``None`` on this path: the
        padded batch rolls past each request's real length, so the
        microbatch carry is not any request's x(T) — use :meth:`submit`
        when the resume carry matters.  A spec's ``x0`` seeds its row of
        the padded batch (rows without one start from zero).
        """
        bucketer = bucketer or PaddingBucketer()
        groups: dict[bool, list] = {}
        tids: dict = {}
        for i, spec in enumerate(specs):
            if spec.model is not None:
                raise ValueError(
                    f"spec routes to model {spec.model!r}; submit through "
                    "a registry-backed server")
            want = self._resolve_want(spec.want_states)
            uid = spec.uid if spec.uid is not None else f"req{i}"
            tids[uid] = spec.trace_id or obs.new_trace_id()
            groups.setdefault(want, []).append(
                RolloutRequest(uid=uid, inputs=np.asarray(spec.inputs),
                               x0=spec.x0))
        results: dict = {}
        dim = self.config.reservoir_dim
        arrival = time.perf_counter()
        for want, reqs in groups.items():
            for mb in bucketer.group(reqs):
                u = jnp.asarray(mb.inputs)
                b, t = u.shape[0], u.shape[1]
                x0b = (jnp.zeros((b, dim), jnp.float32) if mb.x0 is None
                       else jnp.asarray(mb.x0, jnp.float32))
                t0 = time.perf_counter()
                out, _xf = self._dispatch(u, x0b, not want, True, False)
                self._record(out, b, t, t0, mb.real_steps)
                finish = time.perf_counter()
                seconds = finish - t0
                for j, req in enumerate(mb.requests):
                    row = out[j, :req.length]
                    tid = tids[req.uid]
                    obs.span("request.serve", t0, finish, trace_id=tid,
                             clock="wall", batch=b, steps=t)
                    obs.observe("request_latency_seconds", finish - arrival,
                                path="engine")
                    results[req.uid] = RolloutResult(
                        preds=None if want else row,
                        states=row if want else None,
                        timings=lifecycle_timings(
                            arrival_time=arrival, admit_time=t0,
                            finish_time=finish, seconds=seconds,
                            trace_id=tid))
        return results

    # -- deprecated boolean-twin shims (one release) -------------------------
    def rollout(self, inputs: jnp.ndarray,
                x0: jnp.ndarray | None = None,
                real_steps: int | None = None,
                return_final_state: bool = _UNSET, *,
                donate_state: bool = False,
                defer_sync: bool = False):
        """Roll the reservoir: (T, I) -> (T, R) or (B, T, I) -> (B, T, R).

        Passing the deprecated boolean twin (``True`` changes the return
        arity to ``(states, x(T))``) warns: chunked callers belong on
        :meth:`run_segment`, one-shot callers needing the carry on
        :meth:`submit` (``RolloutResult.final_state``).
        """
        with_final = False
        if return_final_state is not _UNSET:
            warn_deprecated(
                "rollout(return_final_state=...) is deprecated: use "
                "run_segment() for chunked serving or "
                "submit(SubmitSpec(...)).final_state for the one-shot "
                "carry")
            with_final = bool(return_final_state)
        u, x0b, single = self._prepare(inputs, x0)
        b, t, _ = u.shape
        t0 = time.perf_counter()
        states, xf = self._dispatch(u, x0b, False, with_final,
                                    donate_state and with_final)
        self._record(states, b, t, t0, real_steps, defer=defer_sync)
        if with_final:
            return (states[0], xf[0]) if single else (states, xf)
        return states[0] if single else states

    def predictions(self, inputs: jnp.ndarray,
                    x0: jnp.ndarray | None = None,
                    real_steps: int | None = None,
                    return_final_state: bool = _UNSET, *,
                    donate_state: bool = False,
                    defer_sync: bool = False):
        """Fused-readout rollout: (B, T, I) -> (B, T, O) predictions.

        ``W_out`` is applied inside the rollout (scan body / Pallas
        epilogue), so the (B, T, R) state trajectory is never materialized.
        The deprecated ``return_final_state`` twin warns exactly like
        :meth:`rollout`'s.
        """
        if self._w_out is None:
            raise ValueError("readout not trained; call fit_readout first "
                             "(or submit with want_states=True)")
        with_final = False
        if return_final_state is not _UNSET:
            warn_deprecated(
                "predictions(return_final_state=...) is deprecated: use "
                "run_segment() for chunked serving or "
                "submit(SubmitSpec(...)).final_state for the one-shot "
                "carry")
            with_final = bool(return_final_state)
        u, x0b, single = self._prepare(inputs, x0)
        b, t, _ = u.shape
        t0 = time.perf_counter()
        preds, xf = self._dispatch(u, x0b, True, with_final,
                                   donate_state and with_final)
        self._record(preds, b, t, t0, real_steps, defer=defer_sync)
        if with_final:
            return (preds[0], xf[0]) if single else (preds, xf)
        return preds[0] if single else preds

    def serve(self, requests: Sequence[RolloutRequest],
              bucketer: PaddingBucketer | None = None,
              return_states: bool | None = _UNSET) -> dict:
        """Deprecated-surface batch serve: {uid: bare ndarray}.

        :meth:`submit_many` is the current contract (same batching, but
        answering ``RolloutResult``); this shim survives one release for
        callers holding ``RolloutRequest`` lists.  Without a trained
        readout it falls back to states; the deprecated ``return_states``
        twin forces the states contract with a warning.
        """
        if return_states is _UNSET:
            return_states = None
        else:
            warn_deprecated(
                "serve(return_states=...) is deprecated: use "
                "submit_many([SubmitSpec(..., want_states=True)]) — "
                "results carry .states/.preds explicitly")
        if return_states is None:
            return_states = not self.has_readout
        specs = [SubmitSpec(req.inputs, x0=req.x0, uid=req.uid,
                            want_states=return_states)
                 for req in requests]
        return {uid: res.output
                for uid, res in self.submit_many(specs, bucketer).items()}


# -- bounded engine cache ----------------------------------------------------
# A long-lived multi-tenant server cycles through many reservoirs; an
# unbounded per-process cache of compiled engines would grow without limit.
# The cache is a module-level LRU with two key regimes:
#
# * registry identity ``((name, version), backend)`` — the multi-tenant
#   contract.  (name, version) is stable across process lifetime, so a
#   republished readout with value-equal arrays can NEVER alias the old
#   version's compiled engine: the version number differs, and the entry's
#   staleness check still guards params/readout identity on top.
# * legacy ``(id(params), backend)`` — the single-model accessor
#   (run_reservoir etc.).  A cached engine holds its params alive, so a
#   live entry's id can never be reused by a different object; after
#   eviction an id *can* recur, which the identity staleness check
#   catches before serving a wrong engine.
#
# Entries are (engine, kwargs-signature) tuples; per-tenant hit/miss
# counters land under ``engine_cache_stats()["tenants"]``.
ENGINE_CACHE_MAX = 32
_engine_cache: "collections.OrderedDict[tuple, tuple]" = \
    collections.OrderedDict()
_engine_cache_stats: dict = {"hits": 0, "misses": 0, "evictions": 0,
                             "tenants": {}}


def _tenant_counters(name) -> dict:
    tenants = _engine_cache_stats["tenants"]
    d = tenants.get(name)
    if d is None:
        d = tenants[name] = {"hits": 0, "misses": 0}
    return d


def engine_cache_stats(reset: bool = False) -> dict:
    """Hit/miss/eviction counters of the ``engine_for`` LRU (plus current
    size and the per-tenant breakdown); ``reset=True`` zeroes them."""
    out = dict(_engine_cache_stats, size=len(_engine_cache))
    out["tenants"] = {name: dict(c)
                      for name, c in _engine_cache_stats["tenants"].items()}
    if reset:
        _engine_cache_stats.update(hits=0, misses=0, evictions=0)
        _engine_cache_stats["tenants"].clear()
    return out


def engine_cache_clear() -> None:
    _engine_cache.clear()


def engine_cache_demote(tenant) -> int:
    """Move every cache entry of ``tenant`` — a registry ``(name,
    version)`` — to the eviction front of the LRU, so a just-retired model
    version is the first thing churn reclaims.  Returns the number of
    entries demoted (the engine stays usable until actually evicted:
    in-flight slots pinned to it finish unaffected)."""
    demoted = 0
    for key in list(_engine_cache):
        if key[0] == tenant:
            _engine_cache.move_to_end(key, last=False)
            demoted += 1
    return demoted


def _cache_put(key: tuple, eng: "ReservoirEngine", sig: tuple) -> None:
    _engine_cache[key] = (eng, sig)
    _engine_cache.move_to_end(key)
    while len(_engine_cache) > ENGINE_CACHE_MAX:
        _engine_cache.popitem(last=False)
        _engine_cache_stats["evictions"] += 1
    _engine_cache_stats["misses"] += 1
    obs.event("engine_cache_miss", key=str(key))
    obs.inc("engine_cache_requests_total", outcome="miss")


def _params_stale(eng: "ReservoirEngine", params: ESNParams) -> bool:
    cfg = params.config
    return (eng.params is not params
            or eng._w_out is not params.w_out
            or eng.params.w is not params.w
            or (eng.config.leak, eng.config.mode, eng.config.state_bits)
            != (cfg.leak, cfg.mode, cfg.state_bits))


def engine_for(params: ESNParams, backend: str = "auto", *,
               tenant=None, build=None, **kwargs) -> ReservoirEngine:
    """Engine accessor with a bounded LRU cache (reservoirs are frozen).

    Without ``tenant`` the key is (id(params), backend) — the
    ``run_reservoir`` fast path — and non-default kwargs bypass the cache.
    With ``tenant`` (a registry ``(name, version)`` tuple) the key is the
    *registry identity*: stable across republishes, so an equal-valued
    readout under a new version can never alias the retired engine, and
    hashable kwargs become part of the cached entry (a config change
    rebuilds).  ``build`` overrides the constructor (the registry passes a
    sharded-engine factory on multi-device servers).

    Every entry is invalidated by what the engine bakes in at construction
    — the reservoir matrix, the *readout* (so a stale compiled rollout is
    never served after ``fit_readout`` replaces ``w_out``), and the
    leak/mode/precision config.  At most :data:`ENGINE_CACHE_MAX` engines
    stay resident (least recently used evicted first), so a multi-tenant
    server's memory is bounded — ``engine_cache_stats()`` exposes the
    hit/miss/eviction counters, globally and per tenant.  NOTE: a cached
    engine holds its params (and compiled programs) alive until it is
    evicted or ``engine_cache_clear()`` runs — the cache trades bounded
    pinning for compile reuse.

    ``backend="auto"`` keys the cache on the backend
    :func:`~repro.plan.schedule.choose_schedule` picks for these params,
    the rule the constructor runs, so the cache key and the built engine's
    backend always agree.
    """
    if backend != "auto":
        bk = backend
    elif not kwargs.get("specialize", True):
        bk = "xla"  # unspecialized engines take no schedule
    else:
        mode = "int8" if params.config.mode.startswith("int8") else "fp32"
        bk = choose_schedule(plan_for(params.w), mode,
                             jax.default_backend()).backend
    if tenant is None:
        key = (id(params), bk)
        ent = _engine_cache.get(key)
        eng = ent[0] if ent is not None else None
        if eng is None or kwargs or _params_stale(eng, params):
            eng = (build or ReservoirEngine)(params, backend=backend,
                                            **kwargs)
            if not kwargs and build is None:
                _cache_put(key, eng, ())
        else:
            _engine_cache.move_to_end(key)
            _engine_cache_stats["hits"] += 1
            obs.inc("engine_cache_requests_total", outcome="hit")
        return eng

    name = tenant[0] if isinstance(tenant, tuple) else tenant
    counters = _tenant_counters(name)
    try:
        sig = tuple(sorted(kwargs.items()))
        hash(sig)
    except TypeError as e:
        raise TypeError(
            "engine_for(tenant=...) caches on the kwargs signature, so "
            f"every kwarg must be hashable: {kwargs}") from e
    key = (tenant, bk)
    ent = _engine_cache.get(key)
    if (ent is not None and ent[1] == sig
            and not _params_stale(ent[0], params)):
        _engine_cache.move_to_end(key)
        _engine_cache_stats["hits"] += 1
        counters["hits"] += 1
        obs.inc("engine_cache_requests_total", outcome="hit", tenant=name)
        return ent[0]
    if build is not None:
        eng = build(params, backend=backend, **kwargs)
    else:
        eng = ReservoirEngine(params, backend=backend, tenant=name, **kwargs)
    _cache_put(key, eng, sig)
    counters["misses"] += 1
    return eng


__all__ = ["ENGINE_CACHE_MAX", "ReservoirEngine", "engine_for",
           "engine_cache_clear", "engine_cache_demote",
           "engine_cache_stats", "ServeStats",
           "PaddingBucketer", "RolloutRequest", "MicroBatch",
           "SubmitSpec", "RolloutResult"]
