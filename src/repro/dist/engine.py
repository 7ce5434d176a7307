"""Batch-axis sharded rollout engine: ``shard_map`` over the ``data`` mesh.

The paper's throughput argument scales by *replication*: the spatial
multiplier is a fixed circuit, so more traffic means stamping more copies
of the same structure, never re-synthesizing it.  The TPU analogue is
data parallelism with zero collectives in the hot loop — the
:class:`~repro.plan.ExecutionPlan` artifacts, ``w_in`` and ``w_out`` are
closure constants replicated once per device, and the batch axis is the
only thing sharded.  Each shard runs the *identical* single-device rollout
callable (:meth:`ReservoirEngine._local_rollout`) on its batch slice, so
the sharded output is bit-identical per sequence to the single-device
engine on both backends: rows never mix through the recurrence, and the
per-row arithmetic is the same compiled program either way.  (One caveat,
pinned by tests: when a shard holds a single row, XLA may lower the
recurrent matmul as a gemv whose accumulation order differs by an ulp —
size the batch to at least two rows per shard for exactness.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.launch.mesh import make_data_mesh
from repro.parallel.sharding import (batch_spec, data_axis_names,
                                     data_axis_size)
from repro.serve.api import _UNSET
from repro.serve.engine import (DENSE_DISPATCH_DENSITY, ReservoirEngine,
                                donated_call)
from repro.serve.stats import ServeStats


class ShardedReservoirEngine(ReservoirEngine):
    """:class:`ReservoirEngine` with the batch dimension sharded on a mesh.

    Same public API (``submit`` / ``rollout`` / ``predictions`` / the
    ``run_segment`` chunk API) and the same compiled per-shard program;
    the only new behavior is batch padding up to a multiple of the shard
    count (padded rows are zero sequences riding along in otherwise-idle
    shard capacity, and never leave the engine).  Padding and trimming run
    inside the sharded program, so no operand is staged on one device.

    Pass a ``mesh`` (any mesh with 'data' — and optionally 'pod' — axes)
    or just ``n_shards`` to build a 1-D data mesh over the first N local
    devices.
    """

    def __init__(self, params, *, mesh=None, n_shards: int | None = None,
                 backend: str = "auto",
                 stats: ServeStats | None = None,
                 dense_dispatch_density: float = DENSE_DISPATCH_DENSITY,
                 vmem_budget: int | None = _UNSET,
                 specialize: bool = True, tenant=None,
                 crossover: int | None = None,
                 batch_tile_max: int | None = None, schedule=None):
        self.mesh = mesh if mesh is not None else make_data_mesh(n_shards)
        assert data_axis_names(self.mesh), \
            f"mesh has no data axes: {self.mesh.axis_names}"
        self.n_shards = data_axis_size(self.mesh)
        self._batch_spec = batch_spec(self.mesh)
        # where a pool's batch-major buffers live: rows split over the
        # data shards, as the sharded rollout consumes them
        self.batch_sharding = NamedSharding(self.mesh, self._batch_spec)
        # kept for elastic rebuilds: shrink() must reconstruct the engine
        # with the same dispatch policy, not the default
        self.dense_dispatch_density = dense_dispatch_density
        # backend="auto" resolves through the plan autotuner in the base
        # constructor — the per-shard program IS the single-device program
        # (shard_map wraps _local_rollout), so the sharded engine inherits
        # the tuned schedule for free.
        super().__init__(params, backend=backend, stats=stats,
                         dense_dispatch_density=dense_dispatch_density,
                         vmem_budget=vmem_budget, specialize=specialize,
                         tenant=tenant, crossover=crossover,
                         batch_tile_max=batch_tile_max, schedule=schedule)
        self._sharded_fns: dict = {}

    def like(self, params=None, *, mesh=None, stats=None, tenant=None):
        """A sibling engine with this one's dispatch policy.

        Elastic rebuilds (new ``mesh``, same params) and multi-tenant
        routing (new ``params``, same mesh) both need "the same engine,
        but for X" — mesh-mapped engines are built per server, not
        through the global ``engine_for`` LRU, because the mesh is part
        of their identity.  Same params carry this engine's resolved
        schedule verbatim; new params re-resolve through the tuner (a
        different matrix has its own schedule space), inheriting the
        tuned-ness rather than this matrix's tuned values."""
        same = params is None or params is self.params
        return ShardedReservoirEngine(
            self.params if params is None else params,
            mesh=self.mesh if mesh is None else mesh,
            backend=self.backend if same else self.requested_backend,
            stats=self.stats if stats is None else stats,
            dense_dispatch_density=self.dense_dispatch_density,
            vmem_budget=self.vmem_budget if same else _UNSET,
            specialize=self.specialize, tenant=tenant,
            crossover=self.crossover if same else None,
            batch_tile_max=self.batch_tile_max if same else None,
            schedule=self.schedule if same else None)

    def _sharded(self, with_readout: bool, with_final: bool,
                 donate: bool = False):
        """jit(pad -> shard_map(local_rollout) -> trim), cached per output
        signature.

        The shard_map body is the *specialized* local rollout callable —
        the sharded path inherits whatever program the plan selected
        (folded int8 gemm, resident/pipelined pallas kernel) for free.
        ``donate`` donates the carried state at the jit boundary, so the
        zero-copy chunk API works sharded too.
        """
        key = (with_readout, with_final, donate)
        fn = self._sharded_fns.get(key)
        if fn is None:
            spec, n = self._batch_spec, self.n_shards
            # check_vma=False: the weights/plan artifacts enter as closure
            # constants (replicated), which the varying-axes checker cannot
            # see through on the pallas path.
            body = jax.shard_map(
                self._local_rollout(with_readout, with_final),
                mesh=self.mesh, in_specs=(spec, spec),
                out_specs=(spec, spec) if with_final else spec,
                check_vma=False)

            def run(u, x0b):
                b = u.shape[0]
                pad = -(-b // n) * n - b
                if pad:
                    u = jnp.pad(u, ((0, pad), (0, 0), (0, 0)))
                    x0b = jnp.pad(x0b, ((0, pad), (0, 0)))
                out = body(u, x0b)
                if not pad:
                    return out
                if with_final:
                    return out[0][:b], out[1][:b]
                return out[:b]

            fn = jax.jit(run, donate_argnums=(1,) if donate else ())
            self._sharded_fns[key] = fn
        return fn

    def _dispatch(self, u, x0b, with_readout: bool, with_final: bool,
                  donate: bool = False):
        fn = self._sharded(with_readout, with_final, donate)
        out = donated_call(fn, u, x0b) if donate else fn(u, x0b)
        return out if with_final else (out, None)

    def _executed_rows(self, batch: int) -> int:
        # shard padding first, then each shard's own batch-tile padding
        per_shard = -(-batch // self.n_shards)
        return self.n_shards * super()._executed_rows(per_shard)
