"""ExecutionPlan -> Pallas rollout launch.

The offline lowering lives in :mod:`repro.plan`: the reservoir matrix is
frozen, so the reduction structure (which blocks exist, which digit
plane-blocks are populated, how the columns band into VMEM) is compiled
once there and consumed here as trace-time constants.  This wrapper only
pads the per-instance operands (w_in, w_out, x0) and dispatches.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.sparse import FixedMatrix
from repro.kernels.reservoir_rollout.reservoir_rollout import reservoir_rollout
from repro.plan import DEFAULT_VMEM_BUDGET, ExecutionPlan, plan_for
from repro.plan.plan import pad_axis


class FusedRollout:
    """Precompiled fused multi-step rollout for one frozen reservoir.

    Offline (init): take the shared :class:`~repro.plan.ExecutionPlan`
    (building it if handed a raw FixedMatrix) and pick the banded rollout
    layout for the requested mode and VMEM budget.  Online (``__call__``):
    one Pallas launch rolls the whole (T, B) workload, state resident in
    VMEM, streaming one band of weight tiles per grid step.

    With ``w_out`` attached, the readout is fused into the launch epilogue
    and ``__call__`` can return predictions instead of (or alongside) the
    state trajectory.
    """

    def __init__(self, source: FixedMatrix | ExecutionPlan, w_in, *,
                 leak: float = 1.0, mode: str = "fp32", state_bits: int = 8,
                 w_out=None,
                 vmem_budget: int | None = DEFAULT_VMEM_BUDGET,
                 readout_every: int = 1):
        plan = source if isinstance(source, ExecutionPlan) else plan_for(source)
        assert plan.shape[0] == plan.shape[1], "reservoir matrix must be square"
        assert mode in ("fp32", "int8"), mode
        assert plan.nbr == plan.nbc
        self.plan = plan
        self.layout = plan.rollout_layout(mode, vmem_budget=vmem_budget)
        self.dim = plan.shape[0]
        self.block = plan.block
        self.rpad = plan.cols_pad
        self.leak = float(leak)
        self.mode = mode
        self.readout_every = int(readout_every)
        self.smax = (1 << (state_bits - 1)) - 1
        self.recur_scale = plan.scale / self.smax
        self.n_terms = self.layout.n_terms
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

    @property
    def n_bands(self) -> int:
        return self.layout.n_bands

    def __call__(self, u_seq: jnp.ndarray, x0: jnp.ndarray | None = None, *,
                 want_states: bool = True, want_preds: bool = False,
                 want_final: bool = False):
        """u_seq: (T, B, I) -> the requested outputs, in order: states
        (T, B, dim), preds (T // readout_every, B, out_dim), final state
        (B, dim).  A bare array when exactly one is requested, else a
        tuple.  ``want_final`` hands back x(T) so a later chunk can
        resume the rollout bit-identically (continuous batching)."""
        assert want_states or want_preds or want_final
        assert not want_preds or self.w_out is not None, \
            "fused readout requested but no w_out attached"
        t, b, _ = u_seq.shape
        if x0 is None:
            x0 = jnp.zeros((b, self.rpad), jnp.float32)
        else:
            x0 = jnp.asarray(x0, jnp.float32)
            x0 = jnp.pad(x0, ((0, 0), (0, self.rpad - x0.shape[1])))
        out = reservoir_rollout(
            u_seq.astype(jnp.float32), self.layout.data, self.w_in, x0,
            self.w_out if want_preds else None,
            band_plans=self.layout.band_plans(), leak=self.leak,
            block=self.block, mode=self.mode, smax=self.smax,
            recur_scale=self.recur_scale, readout_every=self.readout_every,
            want_states=want_states, want_preds=want_preds,
            want_final=want_final)
        parts = list(out) if isinstance(out, tuple) else [out]
        trimmed = []
        if want_states:
            trimmed.append(parts.pop(0)[:, :, : self.dim])
        if want_preds:
            trimmed.append(parts.pop(0)[:, :, : self.out_dim])
        if want_final:
            trimmed.append(parts.pop(0)[:, : self.dim])
        return trimmed[0] if len(trimmed) == 1 else tuple(trimmed)
