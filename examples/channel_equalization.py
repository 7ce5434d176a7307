"""Nonlinear channel equalization with an ESN — the task of paper ref [3].

A 4-PAM symbol stream is distorted by a multipath channel with a memoryless
nonlinearity and additive noise; the reservoir recovers the transmitted
symbol (delay 2).  Reports symbol error rate (SER) for fp32 and for the
paper's int8+CSD fixed-point reservoir, plus the FPGA cost of the deployed
matrix — the latency-per-symbol story is exactly the paper's pitch for
spatial reservoirs.

Run:  PYTHONPATH=src python examples/channel_equalization.py
"""

import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.core.esn import (ESNConfig, fit_readout, init_esn, predict,
                            run_reservoir)
from repro.data.pipeline import channel_equalization

SYMBOLS = np.array([-3.0, -1.0, 1.0, 3.0])


def ser(pred, target):
    pred = np.asarray(pred).ravel()
    snap = SYMBOLS[np.argmin(np.abs(pred[:, None] - SYMBOLS[None, :]), axis=1)]
    return float((snap != np.asarray(target).ravel()).mean())


def main():
    enable_compile_cache()
    n = 6000
    u, d = channel_equalization(n, seed=0, snr_db=28.0)
    u = (u / np.abs(u).max()).astype(np.float32)
    split = 4000

    # per-mode hyperparameters from a small validation sweep
    hp = {"fp32": dict(input_scale=0.3, leak=0.3, spectral_radius=0.8),
          "int8-csd": dict(input_scale=1.0, leak=0.6, spectral_radius=0.85)}
    for mode in ("fp32", "int8-csd"):
        cfg = ESNConfig(reservoir_dim=600, element_sparsity=0.85, mode=mode,
                        seed=3, **hp[mode])
        p = init_esn(cfg)
        states = run_reservoir(p, jnp.asarray(u[:, None]))
        p = fit_readout(p, states[200:split], jnp.asarray(d[200:split, None]),
                        lam=1e-5)
        test = ser(predict(p, states[split:])[:, 0], d[split:])
        cost = p.w.fpga_cost()
        print(f"{mode:9s} SER={test:.4f}  | deployed matrix: "
              f"{p.w.ones} ones, {cost.latency_ns:.0f} ns/symbol, "
              f"{cost.power_w:.1f} W")
        assert test < 0.2  # chance = 0.75


if __name__ == "__main__":
    main()
