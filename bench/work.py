"""Operations and minimum bytes of reservoir steps, and the chip's peaks.

Computed from the configuration's matrix alone: the nonzeros of ``W``
quantized to its stated weight bits, and the input and readout widths.
Nothing here reads a plan, a schedule or a program, so a kernel's roofline
share reads the same work whatever implements it.  Index bytes count 0:
the spatial implementation constant-propagates the sparsity structure
into the circuit (the paper's thesis), so a kernel that reads indices at
run time is paying for something the matrix does not need.

Per step of one row (a multiply-add is two operations):

    ops = 2 * nnz(Q) + 2 * R * I + 2 * R * O

Per launch of ``rows`` rows over ``steps`` steps, the least that must move
between HBM and the chip: each weight once (``nnz`` int8 values, ``W_in``
and ``W_out`` in float32), the state in and out, each input and each
prediction once.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    nnz: int                 # nonzeros of the quantized reservoir matrix
    reservoir_dim: int
    input_dim: int
    output_dim: int
    weight_bytes: int        # bytes per stored nonzero (8-bit: 1)

    @property
    def ops_per_row_step(self) -> int:
        r = self.reservoir_dim
        return 2 * self.nnz + 2 * r * (self.input_dim + self.output_dim)

    def ops(self, rows: int, steps: int) -> int:
        return rows * steps * self.ops_per_row_step

    def bytes(self, rows: int, steps: int) -> int:
        r, i, o = self.reservoir_dim, self.input_dim, self.output_dim
        weights = self.nnz * self.weight_bytes + 4 * r * (i + o)
        state = 2 * 4 * rows * r
        io = 4 * rows * steps * (i + o)
        return weights + state + io


def work_of(q: np.ndarray, cfg: dict) -> Work:
    """The work of the configuration ``cfg`` whose quantized matrix is
    ``q``."""
    return Work(nnz=int(np.count_nonzero(q)),
                reservoir_dim=cfg["reservoir_dim"],
                input_dim=cfg["input_dim"], output_dim=cfg["output_dim"],
                weight_bytes=-(-cfg["weight_bits"] // 8))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``: ``bf16_ops_per_s``,
    ``int8_ops_per_s``, ``hbm_bytes_per_s``.  An unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def compute_peak(device_kind: str, cfg: dict) -> float:
    """Operations per second at the peak of the recurrent operand's type."""
    p = peaks(device_kind)
    return p["int8_ops_per_s"] if cfg["mode"].startswith("int8") \
        else p["bf16_ops_per_s"]


def bound_s(work: Work, rows: int, steps: int, device_kind: str,
            cfg: dict) -> float:
    """The least time one chip could take for one launch: the larger of
    its operations over peak compute and its bytes over peak bandwidth."""
    p = peaks(device_kind)
    return max(work.ops(rows, steps) / compute_peak(device_kind, cfg),
               work.bytes(rows, steps) / p["hbm_bytes_per_s"])
