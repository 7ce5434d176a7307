"""Continuous-batching scheduler: decode-style admission for rollouts.

One-shot ``ReservoirEngine.serve()`` takes a fully-formed request list,
pads it, and blocks until the whole group is rolled.  Under streaming
arrivals that wastes time twice: the batch cannot start until its last
request exists, and every sequence is padded to the group's length bucket.
This module serves the same requests decode-style instead:

* a fixed pool of **batch slots** (the compiled batch dimension never
  changes, so the engine reuses one program for every chunk),
* the engine runs in fixed ``chunk_steps`` segments, and between chunks
  finished sequences **retire** and queued ones are **admitted mid-flight**,
* each live slot's reservoir state is carried across chunks through the
  engine's ``run_segment`` chunk API, so the chunked trajectory is
  bit-identical to a one-shot rollout of the same inputs — the recurrence
  is stateful per sequence, which is exactly what makes reservoir
  continuous batching more than prompt re-padding.

The pool is **multi-tenant**: every slot is tagged with the engine its
request resolved to at admission (via a
:class:`~repro.serve.registry.ModelRegistry`), one FIFO interleaves all
tenants under per-tenant quotas/deadlines, and each chunk issues one
fused call per *active model* at the full pool shape — rows are
independent through the recurrence, so cross-tenant interleaving keeps
every sequence bit-identical to its single-tenant run.

:class:`ContinuousBatcher` owns the slot pool mechanics;
:class:`AsyncReservoirServer` adds the time-stamped arrival queue, the
virtual clock, and queue-wait / time-to-first-prediction / slot-occupancy
telemetry on :class:`~repro.serve.stats.ServeStats` (per tenant too).
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.runtime.faults import TransientFault
from repro.serve.api import (_UNSET, RolloutResult, SubmitSpec,
                             lifecycle_timings, warn_deprecated)
from repro.serve.batching import RolloutRequest
from repro.serve.stats import ServeStats

# stack sizes of admission's pool write: a sweep's stack is padded up to
# the next one, and a sweep seating more than the last writes several.
# None is 1: the TPU compiler turns a one-row update of a sharded pool
# into an all-gather of the whole buffer, where two rows stay local
_STACKS = (2, 4, 8, 16)


def write_stack(constrain, u_dev, states, idx, lanes, rows):
    """Admission's pool write: stack entry ``j`` seats slot ``idx[j]`` with
    state row ``rows[j]`` and, on the zero-copy pool (``u_dev`` not None),
    input lanes ``lanes[j]``.  Out-of-range indices (stack padding) are
    dropped.  ``constrain`` keeps each result where the pool lives."""
    states = constrain(states.at[idx].set(
        rows, mode="drop", unique_indices=True))
    if u_dev is not None:
        u_dev = constrain(u_dev.at[idx].set(
            lanes, mode="drop", unique_indices=True))
    return u_dev, states


@dataclasses.dataclass
class QueuedRequest:
    """A :class:`RolloutRequest` plus its arrival time and lifecycle marks.

    The scheduler fills the ``*_time`` fields as the request moves through
    the system (all on the server's clock): ``admit_time`` when it takes a
    slot, ``first_output_time`` when its first chunk of predictions is
    ready, ``finish_time`` when it retires.  ``submit_wall`` is the host's
    wall clock at submit, stamped only while tracing is on.

    ``deadline`` (absolute, on the same clock) bounds the queue wait: a
    request still queued past it is dropped at the next admission sweep —
    counted in ``ServeStats.timed_out`` — instead of occupying a slot for
    an answer nobody is waiting for anymore.

    ``model`` routes the request to a registry tenant;
    ``pinned_version`` is stamped when the request first seats and sticks
    for its whole life — a live swap never migrates in-flight (or
    shrink-re-admitted) work to the new version.
    """

    request: RolloutRequest
    arrival_time: float = 0.0
    seq: int = 0                         # submission index; FIFO tiebreak
    admit_time: float | None = None
    first_output_time: float | None = None
    finish_time: float | None = None
    deadline: float | None = None
    requeued: bool = False               # back in the queue after a shrink:
    #                                      the next seat is a re-admission
    #                                      and must not double-count stats
    model: str | None = None             # registry tenant (None = default)
    pinned_version: int | None = None    # frozen at first admission
    want_states: bool | None = None      # per-request output contract
    #                                      (None = the pool's default)
    as_result: bool = False              # SubmitSpec submission: answer a
    #                                      RolloutResult, not a bare array
    trace_id: str | None = None          # observability correlation id
    #                                      (threads through every span)
    submit_wall: float | None = None     # perf_counter at submit (traced)

    @property
    def uid(self) -> Any:
        return self.request.uid

    @property
    def length(self) -> int:
        return self.request.length


class _DeviceChunk:
    """One chunk's full (n_slots, cs, O) device output, shared by every
    sequence that rode in it and host-converted at most once — the
    device->host sync happens when the first rider retires, never in the
    chunk loop.

    ``riders`` indexes the entries that point at the chunk, as plain
    ``(slot, entry index)`` ints (a reference to a slot's list would make
    a chunk -> list -> entry -> chunk cycle that pins the device buffer
    until a GC pass), so the sync rewrites only those entries.  At
    conversion every rider's entry becomes its own trimmed row copy, so
    neither the device buffer nor the full-width host array outlives the
    sync (a long-lived rider would otherwise pin pool-width buffers for
    its whole life)."""

    __slots__ = ("dev", "riders")

    def __init__(self, dev):
        self.dev = dev
        self.riders: list[tuple[int, int]] | None = []


class ContinuousBatcher:
    """A fixed pool of batch slots rolled forward ``chunk_steps`` at a time.

    Single-tenant chunks are ONE engine call of the static shape
    ``(n_slots, chunk_steps, input_dim)`` — free slots ride along as zero
    rows — with the pool's reservoir states passed as ``x0`` and the
    post-chunk states carried through ``run_segment``.  Rows are
    independent through the recurrence (the batched matmuls and the
    elementwise epilogue never mix rows), so a sequence's chunked
    trajectory equals its one-shot rollout bit for bit.

    Multi-tenant chunks group the occupied slots by their admission-pinned
    engine and issue one fused call *per active model*, each at the full
    pool shape — the same shape (and therefore the same compiled program
    and the same per-row arithmetic) as the single-tenant chunk, which is
    what keeps cross-tenant interleaving bit-exact.  Post-chunk states
    merge by exact row selection.
    """

    def __init__(self, engine, *, n_slots: int = 8, chunk_steps: int = 16,
                 want_states: bool | None = None,
                 return_states: bool | None = _UNSET,
                 zero_copy: bool | None = None, warm: bool = True,
                 resolver=None):
        assert n_slots >= 1 and chunk_steps >= 1
        self.engine = engine
        self.n_slots = n_slots
        self.chunk_steps = chunk_steps
        if return_states is not _UNSET:
            warn_deprecated(
                "ContinuousBatcher(return_states=...) is deprecated; "
                "pass want_states=...")
            if want_states is None:
                want_states = return_states
        if want_states is None:
            want_states = not engine.has_readout
        self.want_states = want_states
        # admission hook: qreq -> engine (a registry-backed server routes
        # per-tenant here); None pins every slot to the default engine
        self._resolver = resolver
        self._slot_engines = [engine] * n_slots
        # zero-copy chunk serving: request inputs move to the device ONCE
        # at admission (into a resident (n_slots, max_chunks, cs, I)
        # buffer), a single jitted gather assembles each chunk's input
        # on-device, the carried state buffer is donated to each launch,
        # chunk outputs stay device-side, and the only device->host syncs
        # in the hot loop happen at slot retirement (``host_syncs`` counts
        # them).  The hot loop dispatches a constant handful of device
        # ops per chunk, independent of pool size.
        #
        # Default is backend-aware: on an accelerator the elided
        # transfers and deferred syncs are the win; on the CPU backend a
        # "transfer" is a memcpy while every extra dispatch costs real
        # Python/XLA overhead (measured ~2x per-chunk cost), so CPU
        # defaults to the host-assembled path.  Both paths produce
        # identical outputs and both are tested.
        if zero_copy is None:
            zero_copy = jax.default_backend() != "cpu"
        self.zero_copy = zero_copy
        self.host_syncs = 0
        self._in_dim = engine.config.input_dim
        self._dim = engine.config.reservoir_dim
        self._slots: list[QueuedRequest | None] = [None] * n_slots
        self._pos = [0] * n_slots               # steps consumed per slot
        self._chunks: list[list] = [[] for _ in range(n_slots)]
        self._states = self._place(
            jnp.zeros((n_slots, self._dim), jnp.float32))
        self._walked = 0                        # rider entries the syncs
        #                                         rewrote (read when traced)
        # admissions seated but not yet written to the device:
        # (slot, input copy or None, x0 row or None); flush() writes them
        self._staged: list[tuple] = []

        # the pool's own programs, named for the profiler's trace.  The
        # donated write seats a whole stack of admissions in place, so a
        # sweep costs one transfer and one program per stack, not per
        # request, and never copies the whole pooled buffer
        def pool_write(u_dev, states, idx, lanes, rows):
            return write_stack(self._constrain, u_dev, states, idx, lanes,
                               rows)

        def pool_gather(u_dev, idx):
            return self._constrain(u_dev[jnp.arange(u_dev.shape[0]), idx])

        self._pool_write = jax.jit(pool_write, donate_argnums=(0, 1))
        self._max_chunks = 4                    # input lanes; doubles on
        #                                         demand (longer requests)
        self._write_chunks = None               # lane count the write
        #                                         stacks were compiled at
        self._u_dev = None
        if zero_copy:
            self._u_dev = self._place(jnp.zeros(
                (n_slots, self._max_chunks, chunk_steps, self._in_dim),
                jnp.float32))
            self._gather = jax.jit(pool_gather)
        self.last_take: dict = {}               # slot -> steps, last chunk
        self.last_retired_slots: list = []
        self.last_models: dict = {}             # slot -> model, last chunk
        # fault injection (set by the server): transient engine-call
        # failures raised by the plan are retried here with capped
        # exponential backoff; the per-chunk virtual-clock charge and
        # retry count land in last_backoff_s / last_retries for the
        # server to account
        self.fault_plan = None
        self.last_backoff_s = 0.0
        self.last_retries = 0
        if warm:
            self._warm()

    @property
    def return_states(self) -> bool:
        """Deprecated alias of ``want_states`` (kept one release)."""
        return self.want_states

    def _place(self, x):
        """Put a slot-major pool buffer where the engine computes on it
        (the default device; a sharded pool overrides this)."""
        return jnp.asarray(x)

    def _constrain(self, x):
        """In-program twin of :meth:`_place` for a jitted pool op's
        result."""
        return x

    def _place_stack(self, arrays):
        """Send an admission stack to the device, in one call, where every
        pool shard can read it (the default device; the sharded pool
        replicates it over its mesh)."""
        return jax.device_put(arrays)

    def _want_of(self, qreq: QueuedRequest) -> bool:
        return (self.want_states if qreq.want_states is None
                else qreq.want_states)

    def _check_dims(self, engine) -> None:
        cfg = engine.config
        if (cfg.input_dim != self._in_dim
                or cfg.reservoir_dim != self._dim):
            raise ValueError(
                f"engine dims (I={cfg.input_dim}, R={cfg.reservoir_dim}) "
                f"do not match the pool's (I={self._in_dim}, "
                f"R={self._dim}): models sharing a slot pool must share "
                "input/reservoir dims — serve differently-sized models "
                "from separate pools")

    def _warm(self) -> None:
        """Pre-compile the pool's exact chunk program + per-slot ops.

        The batcher owns one static shape for its whole life, so every
        program it will ever run can compile at construction: the
        (donated) chunk rollout, the input gather, and admission's pool
        write at every stack size — none of it lands in the measured
        serving makespan.  (A request longer than any before grows the
        input lanes; the gather and the writes then compile once more.)
        Bypasses the engine's public API so warmup never pollutes
        ``ServeStats`` or the request telemetry.
        """
        if not self.want_states and not self.engine.has_readout:
            return      # run_chunk will raise the clear "readout not
            #             trained" error; nothing sane to warm
        self._warm_writes()
        if self.zero_copy:
            self._gather(self._u_dev, jnp.zeros(self.n_slots, jnp.int32))
        self.warm_engine(self.engine)

    def _warm_writes(self) -> None:
        """Compile the pool write at every stack size for the current lane
        count.  Each warm write's indices are all out of range, so it
        changes nothing in the pool."""
        for k in _STACKS:
            self._write(*self._stack((), k))
        self._write_chunks = self._max_chunks

    def warm_engine(self, engine, want_states: bool | None = None) -> None:
        """Compile ``engine``'s pool-shaped chunk program(s), off the
        serving clock.

        Used at construction for the default engine, and by
        :meth:`ModelRegistry.publish` to compile a *new model version
        behind live traffic* — the swap cutover then costs the scheduler
        nothing.  On the zero-copy path both chunk variants are warmed:
        the donated single-tenant launch and the non-donated variant that
        mixed (multi-model) chunks use.  Bypasses the engine's public API
        so warmup never pollutes ``ServeStats``.
        """
        self._check_dims(engine)
        if want_states is None:
            want_states = (self.want_states if engine.has_readout
                           else True)
        u = jnp.zeros((self.n_slots, self.chunk_steps, self._in_dim),
                      jnp.float32)
        for donate in ((True, False) if self.zero_copy else (False,)):
            x0 = jnp.zeros((self.n_slots, self._dim), jnp.float32)
            out, _xf = engine._dispatch(u, x0, not want_states, True,
                                        donate)
            jax.block_until_ready(out)

    @property
    def live(self) -> int:
        return sum(s is not None for s in self._slots)

    def has_free_slot(self) -> bool:
        return any(s is None for s in self._slots)

    def _free_slot(self) -> int:
        """Pick the free slot to seat the next request in.  Subclass hook:
        the sharded batcher overrides this with least-loaded-shard
        admission."""
        return self._slots.index(None)

    def shard_of(self, slot: int) -> int | None:
        """Which device shard ``slot`` maps to — ``None`` on the
        single-device pool.  Subclass hook: the sharded batcher answers
        the real shard index, and the observability layer uses it to
        label per-shard queue-wait/latency series."""
        return None

    def admit(self, qreq: QueuedRequest) -> int:
        """Seat a request in a free slot (zero state, or its ``x0``).

        The slot is tagged with the engine the request resolves to —
        through the ``resolver`` (registry routing, which also pins the
        model version on the request) or the pool default — and keeps it
        for the request's whole life.

        The host bookkeeping is done at once, so free slots, quotas and
        shard loads read true mid-sweep; the slot's device writes (its
        input lanes and state row) are staged, copied off the caller's
        buffers, for :meth:`flush`.
        """
        eng = (self.engine if self._resolver is None
               else self._resolver(qreq))
        self._check_dims(eng)
        if not self._want_of(qreq) and not eng.has_readout:
            raise ValueError(
                "readout not trained on the serving engine; submit with "
                "want_states=True")
        slot = self._free_slot()
        self._slot_engines[slot] = eng
        self._slots[slot] = qreq
        self._pos[slot] = 0
        self._chunks[slot] = []
        x0 = qreq.request.x0
        self._staged.append((
            slot,
            np.array(qreq.request.inputs, np.float32)
            if self.zero_copy else None,
            None if x0 is None else np.array(x0, np.float32)))
        return slot

    def flush(self) -> tuple[int, int]:
        """Write every staged admission to the device.

        Per stack of up to ``_STACKS[-1]`` seated slots: ONE host->device
        transfer (indices, input lanes on the zero-copy path, state rows)
        and ONE donated pool-write program, so a sweep's cost in device
        calls does not grow with the requests it seats.  Each request's
        whole input, pre-cut into ``chunk_steps`` segments, lands in its
        slot's lane of the resident input buffer.  Lanes double when a
        request is longer than any seen before, decided once per flush
        (shape change -> the gather and the writes re-specialize once,
        then stay cached).  Every reader of the pool buffers flushes
        first.  Returns ``(programs issued, bytes sent host->device)``,
        stack padding included.
        """
        if not self._staged:
            return 0, 0
        staged, self._staged = self._staged, []
        if self.zero_copy:
            self._grow_lanes(max(len(seg) for _s, seg, _x in staged))
        if self._write_chunks != self._max_chunks:
            self._warm_writes()
        top = _STACKS[-1]
        writes = nbytes = 0
        for lo in range(0, len(staged), top):
            part = staged[lo: lo + top]
            stack = self._stack(
                part, next(k for k in _STACKS if k >= len(part)))
            nbytes += sum(a.nbytes for a in stack if a is not None)
            self._write(*stack)
            writes += 1
        return writes, nbytes

    def _grow_lanes(self, steps: int) -> None:
        """Double the input lanes until a ``steps``-long request fits, with
        one reallocation straight to the final lane count."""
        cs = self.chunk_steps
        n_chunks = max(1, -(-steps // cs))
        if n_chunks <= self._max_chunks:
            return
        while n_chunks > self._max_chunks:
            self._max_chunks *= 2
        self._u_dev = self._place(jnp.zeros(
            (self.n_slots, self._max_chunks, cs, self._in_dim),
            jnp.float32).at[:, : self._u_dev.shape[1]].set(self._u_dev))

    def _stack(self, part, k: int) -> tuple:
        """Host arrays of one ``k``-entry pool write seating ``part``.
        Padding entries carry distinct out-of-range indices, which the
        write drops."""
        idx = np.arange(self.n_slots, self.n_slots + k, dtype=np.int32)
        rows = np.zeros((k, self._dim), np.float32)
        lanes = (np.zeros((k, self._max_chunks, self.chunk_steps,
                           self._in_dim), np.float32)
                 if self.zero_copy else None)
        for j, (slot, seg, x0) in enumerate(part):
            idx[j] = slot
            if x0 is not None:
                rows[j] = x0
            if lanes is not None:
                lanes[j].reshape(-1, self._in_dim)[: len(seg)] = seg
        return idx, lanes, rows

    def _write(self, idx, lanes, rows) -> None:
        idx, lanes, rows = self._place_stack((idx, lanes, rows))
        self._u_dev, self._states = self._pool_write(
            self._u_dev, self._states, idx, lanes, rows)

    def run_chunk(self) -> tuple[list[tuple[QueuedRequest, np.ndarray]], int]:
        """Roll every slot ``chunk_steps`` forward.

        Returns ``(retired, real_steps)``: each retiree is
        ``(qreq, output)`` with the full (T_request, O/R) output assembled
        from its chunks, and ``real_steps`` counts the input steps the
        chunk actually consumed (seated slots' remaining lengths, capped
        at ``chunk_steps`` — the occupancy numerator).  Sequences that
        finish inside the chunk stop accumulating output at their real
        length (the recurrence is causal, so the zero-padded tail steps
        cannot reach them).

        Occupied slots are grouped by their admission-pinned
        ``(engine, want_states)`` and the chunk issues one fused
        ``run_segment`` per group, every one at the full pool shape —
        a slot's rows go through exactly the arithmetic they would in a
        single-tenant pool, so interleaving tenants (or running both
        sides of a live swap) is bit-exact.  A single-group chunk is
        byte-for-byte the old fast path: one call, donated carry on the
        zero-copy path.
        """
        self.flush()
        cs = self.chunk_steps
        take: dict[int, int] = {}
        with obs.timed_span("scheduler.gather") as span:
            if self.zero_copy:
                # ONE jitted gather assembles the (n_slots, cs, I) chunk
                # from the device-resident input buffer — no host->device
                # copy and no per-slot dispatch in the hot loop.  Free
                # slots gather lane 0 (stale or zero rows): their output
                # is discarded and their state is re-seeded at admission,
                # so the rows are inert ballast exactly like the zero rows
                # of the host path.
                idx = np.zeros(self.n_slots, np.int32)
                for i, q in enumerate(self._slots):
                    if q is None:
                        continue
                    idx[i] = self._pos[i] // cs
                    take[i] = min(cs, q.length - self._pos[i])
                u = self._gather(self._u_dev, jnp.asarray(idx))
            else:
                u_host = np.zeros((self.n_slots, cs, self._in_dim),
                                  np.float32)
                for i, q in enumerate(self._slots):
                    if q is None:
                        continue
                    seg = np.asarray(
                        q.request.inputs[self._pos[i]:self._pos[i] + cs],
                        np.float32)
                    u_host[i, :len(seg)] = seg
                    take[i] = len(seg)
                u = self._place(u_host)
            if span is not None:
                span.attrs["live"] = len(take)
        # group occupied slots by pinned (engine, contract); slot order
        # inside and across groups is deterministic (dict insertion
        # follows slot index)
        groups: dict = {}
        for i, q in enumerate(self._slots):
            if q is None:
                continue
            eng = self._slot_engines[i]
            want = self._want_of(q)
            groups.setdefault((id(eng), want), (eng, want, []))[2].append(i)
        if not groups:
            # empty pool (direct run_chunk call): keep the old contract of
            # one inert full-pool roll on the default engine
            groups = {None: (self.engine, self.want_states, [])}
        single = len(groups) == 1
        prev = self._states
        new_states = None
        self.last_backoff_s = 0.0
        self.last_retries = 0
        for eng, want, slots in groups.values():
            # zero-copy single group: the carried state buffer is donated
            # to the launch (this batcher owns it and immediately replaces
            # it with xf).  With several groups every call reads ``prev``,
            # so nothing may donate it.  Host syncs stay deferred to
            # retirement either way.  An armed fault plan also disables
            # donation: a failed call must leave the carried state intact
            # for the retry to replay from.
            donate = self.zero_copy and single and self.fault_plan is None
            out, xf = self._faulting_call(
                eng, u, prev, want=want,
                real_steps=sum(take.get(i, 0) for i in slots),
                donate=donate)
            if single:
                new_states = xf
            else:
                # exact row selection: where() copies rows unchanged, so
                # the merge cannot perturb bit-exactness
                sel = np.zeros(self.n_slots, bool)
                sel[slots] = True
                new_states = jnp.where(
                    jnp.asarray(sel)[:, None], xf,
                    prev if new_states is None else new_states)
            if self.zero_copy:
                # the whole device-side chunk buffer is shared by its
                # riders (each remembering its real length); no per-slot
                # device op, no host transfer until a rider retires
                chunk = _DeviceChunk(out)
                for i in slots:
                    entries = self._chunks[i]
                    chunk.riders.append((i, len(entries)))
                    entries.append((chunk, take[i]))
            else:
                self.host_syncs += 1
                with obs.timed_span("scheduler.sync") as span:
                    out_h = np.asarray(out)
                    if span is not None:
                        span.attrs["d2h_bytes"] = out_h.nbytes
                for i in slots:
                    self._chunks[i].append(out_h[i, :take[i]].copy())
        self._states = new_states if new_states is not None else prev
        models = {}
        for i, n in take.items():
            self._pos[i] += n
            models[i] = self._slots[i].model
        retired = []
        retired_slots = []
        # retire in a second pass: a retirement materializes the shared
        # chunk buffer (rewriting every rider's entry), so every rider
        # must have its entry before the first retiree triggers that
        with obs.timed_span("scheduler.retire") as span:
            walked = self._walked
            for i in take:
                q = self._slots[i]
                if self._pos[i] >= q.length:
                    retired.append((q, self._assemble(i)))
                    retired_slots.append(i)
                    self._slots[i] = None
                    self._chunks[i] = []
                    self._slot_engines[i] = self.engine
            if span is not None:
                span.attrs.update(retired=len(retired),
                                  entries_walked=self._walked - walked)
        # per-slot view of the chunk just run, for per-shard/tenant
        # telemetry
        self.last_take = dict(take)
        self.last_retired_slots = retired_slots
        self.last_models = models
        return retired, sum(take.values())

    def _faulting_call(self, eng, u, prev, *, want, real_steps, donate):
        """One fused chunk launch under the (optional) fault plan.

        An injected :class:`~repro.runtime.faults.TransientFault` is
        retried with capped exponential backoff *from the slot's last
        carried state*: ``u`` and ``prev`` are untouched by the failed
        attempt (donation is disabled while a plan is armed), so the
        retry runs the exact same program on the exact same operands —
        a bit-identical replay, not a best-effort one.  The accumulated
        backoff lands in ``last_backoff_s`` for the server to charge to
        its virtual clock.
        """
        fp = self.fault_plan
        if fp is None:
            return eng.run_segment(u, prev, want_states=want,
                                   real_steps=real_steps,
                                   donate_state=donate,
                                   defer_sync=self.zero_copy)
        attempt = 0
        while True:
            try:
                fp.check_call()
                return eng.run_segment(u, prev, want_states=want,
                                       real_steps=real_steps,
                                       donate_state=donate,
                                       defer_sync=self.zero_copy)
            except TransientFault:
                if attempt >= fp.max_attempts:
                    raise
                self.last_backoff_s += fp.backoff_s(attempt)
                self.last_retries += 1
                attempt += 1
                obs.inc("engine_call_retries_total")

    def _materialize(self, chunk: _DeviceChunk) -> None:
        """THE deferred device->host sync point, paid once per chunk
        buffer no matter how many riders retire from it, and only ever
        reached from retirement/snapshot paths.  Only the chunk's riders
        are visited (at most ``n_slots``, never every slot's list), and
        each rider's entry is rewritten to its own trimmed row copy, so
        the full-width buffer (device AND host) is immediately
        collectable — a long-lived rider never pins pool-width chunk
        buffers.  An entry no longer pointing at the chunk (its slot was
        vacated and reseated) is left alone."""
        with obs.timed_span("scheduler.sync") as span:
            host = np.asarray(chunk.dev)
            if span is not None:
                span.attrs["d2h_bytes"] = host.nbytes
        chunk.dev = None
        self.host_syncs += 1
        riders, chunk.riders = chunk.riders, None
        self._walked += len(riders)
        for s, j in riders:
            entries = self._chunks[s]
            if j < len(entries) and entries[j][0] is chunk:
                n = entries[j][1]
                entries[j] = (host[s, :n].copy(), n)

    def _slot_rows(self, slot: int) -> list:
        """A slot's chunk outputs as trimmed host rows (zero-copy path),
        materializing any still-device-side buffers."""
        entries = self._chunks[slot]
        for idx in range(len(entries)):
            c, _n = entries[idx]
            if isinstance(c, _DeviceChunk):
                self._materialize(c)            # rewrites entries[idx]
        return [row for row, _n in entries]

    def remaining_inputs(self, slot: int) -> np.ndarray:
        """A live slot's not-yet-consumed input steps, (T_left, I) float32.

        On the zero-copy path the device-resident lane is the source of
        truth — the caller's host buffer was free to be reused the moment
        ``admit()`` uploaded it, so the elastic-shrink snapshot must NOT
        re-read it."""
        self.flush()
        q = self._slots[slot]
        lo = self._pos[slot]
        if not self.zero_copy:
            return np.asarray(q.request.inputs, np.float32)[lo:]
        cs = self.chunk_steps
        n_chunks = max(1, -(-q.length // cs))
        flat = np.asarray(self._u_dev[slot, :n_chunks]).reshape(
            n_chunks * cs, self._in_dim)
        return flat[lo: q.length]

    def chunk_outputs(self, slot: int) -> list:
        """Host copies of a live slot's chunks so far (syncs; used by the
        elastic-shrink snapshot, not the hot loop)."""
        if self.zero_copy:
            return self._slot_rows(slot)
        return list(self._chunks[slot])

    def _assemble(self, slot: int) -> np.ndarray:
        """Concatenate a retiring slot's chunks into its full output.

        On the zero-copy path the underlying buffers sync (at most once
        each) here — at retirement, never in the chunk loop."""
        if self.zero_copy:
            return np.concatenate(self._slot_rows(slot), axis=0)
        return np.concatenate(self._chunks[slot], axis=0)


class AsyncReservoirServer:
    """Time-stamped request queue in front of a :class:`ContinuousBatcher`.

    ``submit()`` enqueues requests with arrival timestamps;  ``run()``
    (or repeated ``step()`` calls) drains the queue: admit every arrived
    request that fits the pool, roll one chunk, retire finished sequences,
    repeat.  Admission is strictly FIFO in (arrival_time, submission
    order), except that a request held back only by its tenant's
    concurrency quota steps aside for later arrivals (it stays queued and
    is re-considered every sweep).

    Attach a :class:`~repro.serve.registry.ModelRegistry` to serve many
    models from one pool: a :class:`~repro.serve.api.SubmitSpec` with
    ``model="name"`` resolves (and pins) the registry's active version at
    admission, the chunk loop groups slots per model, and per-tenant
    telemetry lands in ``tenant_stats``.  ``registry.publish()`` swaps a
    model live: in-flight slots keep their pinned engine, new admissions
    take the new one.

    The server keeps a virtual clock ``now``: it advances by each chunk's
    measured wall time (or the fixed ``chunk_time`` if given — useful for
    deterministic tests and trace-driven benchmarks) and jumps forward to
    the next arrival when the pool runs empty.  Queue waits,
    time-to-first-prediction and slot occupancy land in ``stats``.
    """

    def __init__(self, engine, *, n_slots: int = 8, chunk_steps: int = 16,
                 want_states: bool | None = None,
                 return_states: bool | None = _UNSET,
                 stats: ServeStats | None = None,
                 chunk_time: float | None = None,
                 batcher: ContinuousBatcher | None = None,
                 zero_copy: bool | None = None,
                 registry=None, admission=None, fault_plan=None):
        if return_states is not _UNSET:
            warn_deprecated(
                "AsyncReservoirServer(return_states=...) is deprecated; "
                "pass want_states=... (or set want_states per request on "
                "SubmitSpec)")
            if want_states is None:
                want_states = return_states
        if batcher is None:
            batcher = ContinuousBatcher(
                engine, n_slots=n_slots, chunk_steps=chunk_steps,
                want_states=want_states, zero_copy=zero_copy,
                resolver=self._resolve_engine)
        elif batcher._resolver is None:
            batcher._resolver = self._resolve_engine
        self.batcher = batcher
        self.stats = stats if stats is not None else engine.stats
        self.chunk_time = chunk_time
        self.now = 0.0
        self.results: dict[Any, Any] = {}
        self._queue: list[tuple[float, int, QueuedRequest]] = []
        self._seq = 0
        self.registry = None
        self.tenant_stats: dict[str, ServeStats] = {}
        # backpressure: an AdmissionPolicy consulted at submit time; None
        # keeps the historical accept-everything FIFO
        self.admission = admission
        # fault injection: the plan is driven by this server's clock and
        # consulted by the batcher's chunk launches
        self.fault_plan = fault_plan
        self.batcher.fault_plan = fault_plan
        if registry is not None:
            registry.attach(self)

    # -- multi-tenant plumbing -----------------------------------------------
    def _tstats(self, model: str | None) -> ServeStats | None:
        if model is None:
            return None
        st = self.tenant_stats.get(model)
        if st is None:
            st = self.tenant_stats[model] = ServeStats()
        return st

    def tenant_summary(self) -> ServeStats:
        """Per-tenant breakdown merged into one view (``.shards`` keyed by
        model name)."""
        names = sorted(self.tenant_stats)
        return ServeStats.merge([self.tenant_stats[n] for n in names],
                                labels=names)

    def _tenant_engine(self, name: str, version: int):
        """Engine for a pinned (model, version) — the seam the sharded
        server overrides to build mesh-mapped engines instead."""
        return self.registry.engine(name, version)

    def _resolve_engine(self, qreq: QueuedRequest):
        """Admission-time routing: pin the model's active version to the
        request (a later ``publish()`` must not migrate it) and return its
        engine."""
        if qreq.model is None or self.registry is None:
            return self.batcher.engine
        if qreq.pinned_version is None:
            qreq.pinned_version = self.registry.active_version(qreq.model)
        return self._tenant_engine(qreq.model, qreq.pinned_version)

    def prewarm_model(self, name: str, version: int):
        """Build + compile a model version against this pool's shapes
        before any request routes to it — ``publish()`` calls this on
        every attached server so cutover never compiles under traffic."""
        eng = self._tenant_engine(name, version)
        self.batcher.warm_engine(eng)
        return eng

    # -- queue ---------------------------------------------------------------
    def submit(self, request, arrival_time: float | None = None,
               deadline: float | None = None) -> QueuedRequest:
        """Enqueue one :class:`SubmitSpec`; ``arrival_time`` defaults to
        ``now``.

        ``deadline`` (or ``spec.deadline``, which wins) is an absolute
        time on the server's clock: a request still waiting in the queue
        past it is dropped (``timed_out`` in stats) rather than seated.
        A request already in a slot always runs to completion.  A spec
        naming a ``model`` routes through the attached registry and
        inherits its per-tenant deadline policy when neither deadline is
        given.

        Passing a bare :class:`RolloutRequest` still works for one
        release (with a DeprecationWarning) and answers with the raw
        output array; specs answer with :class:`RolloutResult`.

        When an :class:`~repro.serve.admission.AdmissionPolicy` is
        attached it is consulted here, before the request joins the
        queue: a refusal answers immediately with a
        ``RolloutResult(status="rejected")`` (reason + ``retry_after_s``
        hint in ``timings``) instead of a :class:`QueuedRequest` —
        bounded backpressure, never silent unbounded queueing.
        """
        at = self.now if arrival_time is None else float(arrival_time)
        if isinstance(request, SubmitSpec):
            spec = request
            if spec.model is not None and self.registry is None:
                raise ValueError(
                    f"SubmitSpec routes to model {spec.model!r} but this "
                    "server has no registry attached")
            uid = spec.uid if spec.uid is not None else f"req{self._seq}"
            dl = spec.deadline if spec.deadline is not None else deadline
            if dl is None and spec.model is not None:
                rel = self.registry.deadline_s(spec.model)
                if rel is not None:
                    dl = at + rel
            qreq = QueuedRequest(
                RolloutRequest(uid, np.asarray(spec.inputs, np.float32),
                               x0=spec.x0),
                arrival_time=at, seq=self._seq,
                deadline=None if dl is None else float(dl),
                model=spec.model, want_states=spec.want_states,
                as_result=True,
                trace_id=spec.trace_id or obs.new_trace_id())
        else:
            warn_deprecated(
                "submit(RolloutRequest, ...) is deprecated; submit a "
                "SubmitSpec (results become RolloutResult — read .output)")
            qreq = QueuedRequest(request, arrival_time=at, seq=self._seq,
                                 deadline=None if deadline is None
                                 else float(deadline),
                                 trace_id=obs.new_trace_id())
        self._seq += 1
        if obs.tracer() is not None:
            qreq.submit_wall = time.perf_counter()
        if self.admission is not None:
            verdict = self.admission.admit(self, qreq)
            if verdict is not None:
                return self._reject(qreq, verdict)
        heapq.heappush(self._queue, (at, qreq.seq, qreq))
        self.stats.record_enqueue()
        obs.inc("requests_submitted_total",
                **({} if qreq.model is None else {"model": qreq.model}))
        obs.span("request.enqueue", at, trace_id=qreq.trace_id,
                 clock="server", uid=str(qreq.uid), model=qreq.model)
        ts = self._tstats(qreq.model)
        if ts is not None:
            ts.record_enqueue()
        return qreq

    def _reject(self, qreq: QueuedRequest, verdict) -> RolloutResult:
        """Refuse one submission at the door: count it (``rejected`` or
        ``shed``), emit the obs metric, and answer an explicit
        ``status="rejected"`` result carrying the reason and the
        policy's retry-after hint.  The request never enters the queue
        and never appears in ``enqueued``/``timed_out``."""
        self.stats.record_rejection(shed=verdict.shed)
        labels = {} if qreq.model is None else {"model": qreq.model}
        obs.inc("requests_shed_total" if verdict.shed
                else "requests_rejected_total",
                reason=verdict.reason, **labels)
        obs.span("request.reject", self.now, trace_id=qreq.trace_id,
                 clock="server", uid=str(qreq.uid), reason=verdict.reason)
        ts = self._tstats(qreq.model)
        if ts is not None:
            ts.record_rejection(shed=verdict.shed)
        timings = lifecycle_timings(
            arrival_time=qreq.arrival_time, admit_time=qreq.arrival_time,
            finish_time=qreq.arrival_time, model=qreq.model,
            trace_id=qreq.trace_id)
        timings["reason"] = verdict.reason
        timings["retry_after_s"] = float(verdict.retry_after_s)
        result = RolloutResult(timings=timings, status="rejected")
        self.results[qreq.uid] = result
        return result

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def drained(self) -> bool:
        return not self._queue and self.batcher.live == 0

    def _over_quota(self, qreq: QueuedRequest) -> bool:
        """Would seating this request push its tenant past its registry
        concurrency quota (live slots of the same model)?"""
        if qreq.model is None or self.registry is None:
            return False
        quota = self.registry.quota(qreq.model)
        if quota is None:
            return False
        live = sum(1 for q in self.batcher._slots
                   if q is not None and q.model == qreq.model)
        return live >= quota

    def _timeout(self, qreq: QueuedRequest) -> None:
        """Bookkeeping for one queued request dropped past its deadline."""
        self.stats.record_timeout()
        obs.inc("requests_timed_out_total",
                **({} if qreq.model is None else {"model": qreq.model}))
        obs.span("request.timeout", self.now, trace_id=qreq.trace_id,
                 clock="server", uid=str(qreq.uid))
        ts = self._tstats(qreq.model)
        if ts is not None:
            ts.record_timeout()

    def _drop_expired(self) -> None:
        """Drop every *arrived* queued request whose deadline has passed.

        Called on every clock advance — not only at admission sweeps.
        The sweep in :meth:`_admit_arrived` only examines the queue head
        while slots are free, so a request waiting behind a live head
        (pool full) used to linger past its deadline until a slot freed;
        this catches it the step its deadline passes."""
        expired = [entry for entry in self._queue
                   if (entry[2].deadline is not None
                       and entry[0] <= self.now
                       and self.now > entry[2].deadline)]
        if not expired:
            return
        dropped = {id(entry[2]) for entry in expired}
        self._queue = [entry for entry in self._queue
                       if id(entry[2]) not in dropped]
        heapq.heapify(self._queue)
        for _, _, qreq in expired:
            self._timeout(qreq)

    def _admit_arrived(self) -> None:
        with obs.timed_span("scheduler.admit") as span:
            seated, writes, h2d = self._admit_sweep()
            if span is not None:
                span.attrs.update(admitted=len(seated), writes=writes,
                                  h2d_bytes=h2d)

    def _admit_sweep(self) -> tuple[list, int, int]:
        """Seat every arrived request the pool and quotas allow, then
        write them to the device in one :meth:`ContinuousBatcher.flush`;
        returns the seated, the pool-write programs and the bytes sent.
        A first seating of a request submitted while tracing was on
        records its wall-clock ``request.wait`` from submit."""
        seated = []
        held: list[tuple[float, int, QueuedRequest]] = []
        while self._queue and self._queue[0][0] <= self.now:
            qreq = self._queue[0][2]
            if qreq.deadline is not None and self.now > qreq.deadline:
                # expired while queued: drop it instead of rolling steps
                # nobody is waiting for anymore
                heapq.heappop(self._queue)
                self._timeout(qreq)
                continue
            if not self.batcher.has_free_slot():
                break
            if self._over_quota(qreq):
                # set the request aside for this sweep so tenants under
                # quota seat past it — it rejoins the queue (original
                # FIFO key) for the next sweep
                held.append(heapq.heappop(self._queue))
                self.stats.record_quota_hold()
                obs.inc("quota_holds_total",
                        **({} if qreq.model is None
                           else {"model": qreq.model}))
                ts = self._tstats(qreq.model)
                if ts is not None:
                    ts.record_quota_hold()
                continue
            heapq.heappop(self._queue)
            qreq.admit_time = self.now
            slot = self.batcher.admit(qreq)
            seated.append(qreq)
            if qreq.requeued:
                qreq.requeued = False
            else:
                wait = self.now - qreq.arrival_time
                self.stats.record_admission(wait)
                obs.observe("queue_wait_seconds", wait,
                            **self._obs_labels(qreq, slot))
                obs.span("request.queued", qreq.arrival_time, self.now,
                         trace_id=qreq.trace_id, clock="server",
                         uid=str(qreq.uid), slot=slot)
                if qreq.submit_wall is not None:
                    obs.span("request.wait", qreq.submit_wall,
                             time.perf_counter(), trace_id=qreq.trace_id,
                             uid=str(qreq.uid), slot=slot)
                ts = self._tstats(qreq.model)
                if ts is not None:
                    ts.record_admission(wait)
        for entry in held:
            heapq.heappush(self._queue, entry)
        return (seated, *self.batcher.flush())

    # -- results -------------------------------------------------------------
    def _obs_labels(self, qreq: QueuedRequest, slot: int | None) -> dict:
        """Metric labels for one request: tenant when routed, shard when
        the pool is sharded (nothing otherwise — unlabeled series merge
        naturally)."""
        labels: dict = {}
        if qreq.model is not None:
            labels["model"] = qreq.model
        if slot is not None:
            shard = self.batcher.shard_of(slot)
            if shard is not None:
                labels["shard"] = shard
        return labels

    def _package(self, qreq: QueuedRequest, out) -> Any:
        """Raw array for legacy RolloutRequest submissions, RolloutResult
        for specs.  Timings follow the one documented schema
        (:func:`~repro.serve.api.lifecycle_timings`): ``first_output_time``
        comes straight off the request's lifecycle mark — including marks
        from chunks long before retirement — with retirement as the
        one-chunk-request fallback."""
        if not qreq.as_result:
            return out
        want = self.batcher._want_of(qreq)
        return RolloutResult(preds=None if want else out,
                             states=out if want else None,
                             timings=lifecycle_timings(
                                 arrival_time=qreq.arrival_time,
                                 admit_time=qreq.admit_time,
                                 finish_time=qreq.finish_time,
                                 first_output_time=qreq.first_output_time,
                                 model=qreq.model,
                                 version=qreq.pinned_version,
                                 trace_id=qreq.trace_id))

    # -- event loop ----------------------------------------------------------
    def _handle_faults(self) -> None:
        """Fault-plan hook between clock activation and admission.  The
        base pool has no shards to lose (transient failures are retried
        inside the batcher, straggler windows charged at clock advance);
        the distributed server overrides this to convert activated shard
        deaths into the elastic ``shrink()`` path."""

    def step(self) -> bool:
        """Admit + one chunk + retire.  Returns False once drained.

        Traced, the step is one ``scheduler.step`` span (``chunk``: whether
        a chunk ran) over its phases: ``scheduler.admit``, then the
        chunk's ``scheduler.gather``, ``engine.dispatch`` and
        ``scheduler.retire`` (with its ``scheduler.sync`` waits), then
        ``scheduler.deliver``."""
        with obs.timed_span("scheduler.step") as span:
            alive, chunk = self._step()
            if span is not None:
                span.attrs["chunk"] = chunk
        return alive

    def _step(self) -> tuple[bool, bool]:
        """:meth:`step`'s work; returns (not drained, a chunk ran)."""
        if self.drained:
            return False, False
        if self.batcher.live == 0 and self._queue:
            # pool idle: fast-forward the clock to the next arrival
            self.now = max(self.now, self._queue[0][0])
        if self.fault_plan is not None:
            self.fault_plan.begin_chunk(self.now)
            self._handle_faults()
        self._admit_arrived()
        if self.batcher.live == 0:
            # everything at the head expired (or only future arrivals are
            # left): no chunk to run this step
            return not self.drained, False
        t0 = time.perf_counter()
        retired, real_steps = self.batcher.run_chunk()
        wall = time.perf_counter() - t0
        with obs.timed_span("scheduler.deliver"):
            self._deliver(retired, real_steps, wall)
        return True, True

    def _deliver(self, retired: list, real_steps: int, wall: float) -> None:
        """After a chunk: advance the clock by its charge, sweep deadlines,
        count it, and answer and mark its requests."""
        dt = wall if self.chunk_time is None else self.chunk_time
        if self.fault_plan is not None:
            # straggler windows inflate the chunk's charge; retry backoff
            # from transient failures is time the requests really waited
            dt = dt * self.fault_plan.slow_factor() \
                + self.batcher.last_backoff_s
            for _ in range(self.batcher.last_retries):
                self.stats.record_retry()
        self.now += dt
        # deadlines are checked on every clock advance, not only at
        # admission sweeps — an expired request must not linger behind a
        # full pool
        self._drop_expired()
        self.stats.record_chunk(
            live_steps=real_steps,
            total_steps=self.batcher.n_slots * self.batcher.chunk_steps)
        obs.observe("chunk_seconds", wall)
        # per-slot shard labels for this chunk's retirees (run_chunk
        # already freed their slots, so read its per-chunk view)
        retired_slot = dict(zip((q.uid for q, _ in retired),
                                self.batcher.last_retired_slots))
        slot_of = {q.uid: i for i, q in enumerate(self.batcher._slots)
                   if q is not None}
        slot_of.update(retired_slot)
        for qreq, out in retired:
            qreq.finish_time = self.now
            latency = self.now - qreq.arrival_time
            self.results[qreq.uid] = self._package(qreq, out)
            self.stats.record_completion(latency)
            labels = self._obs_labels(qreq, slot_of.get(qreq.uid))
            obs.observe("request_latency_seconds", latency,
                        path="scheduler", **labels)
            obs.inc("requests_completed_total", **labels)
            obs.span("request.serve", qreq.admit_time, self.now,
                     trace_id=qreq.trace_id, clock="server",
                     uid=str(qreq.uid), **labels)
            ts = self._tstats(qreq.model)
            if ts is not None:
                ts.record_completion(latency)
        # first-output marks: every seated-or-just-retired request that has
        # produced output by the end of this chunk
        for qreq in list(self.batcher._slots) + [q for q, _ in retired]:
            if (qreq is not None and qreq.first_output_time is None
                    and qreq.admit_time is not None):
                qreq.first_output_time = self.now
                ttfp = self.now - qreq.arrival_time
                self.stats.record_first_output(ttfp)
                labels = self._obs_labels(qreq, slot_of.get(qreq.uid))
                obs.observe("ttfp_seconds", ttfp, **labels)
                obs.span("request.first_output", self.now,
                         trace_id=qreq.trace_id, clock="server",
                         uid=str(qreq.uid))
                ts = self._tstats(qreq.model)
                if ts is not None:
                    ts.record_first_output(ttfp)
                res = self.results.get(qreq.uid)
                if isinstance(res, RolloutResult):
                    res.timings["first_output_time"] = self.now
                    res.timings["ttfp_s"] = ttfp

    def run(self) -> dict:
        """Drain the queue; returns ``{uid: RolloutResult}`` (raw arrays
        for legacy RolloutRequest submissions)."""
        while self.step():
            pass
        return self.results


__all__ = ["QueuedRequest", "ContinuousBatcher", "AsyncReservoirServer"]
