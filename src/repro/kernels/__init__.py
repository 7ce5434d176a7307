"""Pallas TPU kernels for the paper's compute hot-spots.

- ``bitplane_gemv``: digit-plane fixed-matrix gemv (bit-serial analogue)
- ``bcsr_matmul``: static block-culled sparse matmul (constant propagation)
- ``reservoir_step``: fused ESN state update (the recurrent latency path)
- ``reservoir_rollout``: T fused steps for a whole batch — state resident
  in VMEM across the scan, static BCSR + digit-plane culling, fp32 and
  exact-int8 modes (serving hot path behind ``repro.serve``)

All kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling).
``platform.pallas_interpret`` derives how they run from the platform:
compiled on the TPU, interpreted on the CPU, where the tests hold them to
pure-jnp oracles.
EXAMPLE.md documents the per-kernel layout convention.
"""
