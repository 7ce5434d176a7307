"""Compile the serving path's rollout programs for a described TPU v5e.

Nothing runs: each case lowers one program for a v5e chip that is
described, not attached, and compiles it with the TPU compiler installed
beside jax.  That catches what the Pallas interpreter cannot — int32 MXU
operands, scatters Mosaic cannot lower, blocks off the (8, 128) tiling —
at no chip time.  The topology is described inside a fixture (never at
import), so every test worker collects the same tests and only the one
that runs this file loads the TPU library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.esn_paper import LARGE_1024
from repro.core.esn import ESNConfig, init_esn
from repro.kernels.reservoir_rollout.ops import FusedRollout
from repro.kernels.reservoir_rollout.specialized import SpecializedRollout
from repro.plan import plan_for
from repro.serve.engine import ReservoirEngine
from repro.serve.scheduler import _STACKS, write_stack

T = 16                      # chunk_steps of the served pool


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernels derive ``interpret`` from ``jax.default_backend()``, which
    is the CPU here; the described chip is a TPU, so lower as on one.
    Traces are cached per jitted kernel, so clear them on the way in and
    out: no CPU trace is reused for the chip, and no chip trace is left
    for a later CPU test in this worker."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    monkeypatch.undo()
    jax.clear_caches()


_PARAMS: dict = {}


def _params(cfg: ESNConfig):
    if cfg not in _PARAMS:
        _PARAMS[cfg] = init_esn(cfg)
    return _PARAMS[cfg]


def _with_readout(params):
    w_out = jnp.zeros((params.config.reservoir_dim,
                       params.config.output_dim), jnp.float32)
    return dataclasses.replace(params, w_out=w_out)


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile_specialized(cfg, batch, sharding, **kw):
    p = _params(cfg)
    mode = "int8" if cfg.mode.startswith("int8") else "fp32"
    w_out = np.zeros((cfg.reservoir_dim, cfg.output_dim), np.float32)
    spec = SpecializedRollout(plan_for(p.w), p.w_in, leak=cfg.leak,
                              mode=mode, w_out=w_out, **kw)
    b_tile = spec.program.batch_tiling(batch)[0]
    compiled = spec._fn(True).lower(
        _sds((T, batch, cfg.input_dim), sharding),
        _sds((batch, cfg.reservoir_dim), sharding),
        want_states=False, want_preds=True, want_final=True,
        b_tile=b_tile).compile()
    return spec.program, compiled.as_text()


def test_int8_csd_dim1024_resident(one_chip, on_tpu):
    program, text = _compile_specialized(LARGE_1024, 64, one_chip)
    assert program.regime == "resident"
    assert "tpu_custom_call" in text


def test_fp32_dim2048_pipelined(one_chip, on_tpu):
    cfg = ESNConfig(reservoir_dim=2048, element_sparsity=0.85)
    program, text = _compile_specialized(cfg, 64, one_chip)
    assert program.regime == "pipelined" and program.n_bands > 1
    assert "tpu_custom_call" in text


def test_int8_shift_add_terms(one_chip, on_tpu):
    cfg = ESNConfig(reservoir_dim=512, element_sparsity=0.98,
                    mode="int8-csd")
    program, text = _compile_specialized(cfg, 8, one_chip)
    assert program.n_shiftadd_terms > 0
    assert "tpu_custom_call" in text


def test_batch_20_tiles_on_sublanes(one_chip, on_tpu):
    cfg = ESNConfig(reservoir_dim=512, element_sparsity=0.9, mode="int8-csd")
    program, text = _compile_specialized(cfg, 20, one_chip)
    b_tile, n_tiles, _ = program.batch_tiling(20)
    assert b_tile % 8 == 0 and n_tiles > 1
    assert "tpu_custom_call" in text


def test_generic_banded_int8_kernel(one_chip, on_tpu):
    p = _params(LARGE_1024)
    fused = FusedRollout(plan_for(p.w), p.w_in, mode="int8")
    u = _sds((T, 64, LARGE_1024.input_dim), one_chip)
    x0 = _sds((64, LARGE_1024.reservoir_dim), one_chip)
    text = jax.jit(lambda u, x0: fused(u, x0, want_final=True)).lower(
        u, x0).compile().as_text()
    assert "tpu_custom_call" in text


def test_xla_engine_rollout(one_chip, on_tpu):
    cfg = LARGE_1024
    eng = ReservoirEngine(_with_readout(_params(cfg)), backend="xla")
    assert eng.xla_schedule == "int8-folded-dense"
    u = _sds((64, T, cfg.input_dim), one_chip)
    x0 = _sds((64, cfg.reservoir_dim), one_chip)
    text = eng._local_rollout(True, True).lower(u, x0).compile().as_text()
    assert "tpu_custom_call" not in text        # plain XLA, no kernel


def test_xla_scattered_table_rollout(one_chip, on_tpu):
    """A degree-3 reservoir with one subdomain's inputs and outputs of
    Pathak et al.'s parallel scheme (20 in, 8 out): the scattered table's
    gather compiles for the chip, at a narrow and at the served pool's
    width."""
    cfg = ESNConfig(reservoir_dim=1280, element_sparsity=1 - 3 / 1280,
                    input_dim=20, output_dim=8, mode="int8-csd")
    eng = ReservoirEngine(_with_readout(_params(cfg)), backend="xla")
    assert eng.xla_schedule == "int8-folded-culled"
    assert eng._int8_summary()["kind"] == "scattered"
    for rows in (8, 256):
        u = _sds((rows, T, cfg.input_dim), one_chip)
        x0 = _sds((rows, cfg.reservoir_dim), one_chip)
        text = eng._local_rollout(True, True).lower(u, x0).compile().as_text()
        assert "tpu_custom_call" not in text    # plain XLA, no kernel


def test_sharded_pool_write_is_shard_local(topo):
    """Admission's pool write over a four-chip pool of the served shape
    (256 slots per chip, 256 input lanes): at every stack size each chip
    updates its own slots, with no collective.  (A one-row stack is not
    among the sizes: the compiler gathers the whole pool for it.)"""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    pool = NamedSharding(mesh, PartitionSpec("data"))
    stack = NamedSharding(mesh, PartitionSpec())
    lanes = (256, T, LARGE_1024.input_dim)
    write = jax.jit(lambda *a: write_stack(
        lambda x: jax.lax.with_sharding_constraint(x, pool), *a),
        donate_argnums=(0, 1))
    for k in _STACKS:
        text = write.lower(
            _sds((1024,) + lanes, pool),
            _sds((1024, LARGE_1024.reservoir_dim), pool),
            jax.ShapeDtypeStruct((k,), jnp.int32, sharding=stack),
            _sds((k,) + lanes, stack),
            _sds((k, LARGE_1024.reservoir_dim), stack)).compile().as_text()
        assert "scatter" in text
        for op in ("all-gather", "all-reduce", "collective-permute",
                   "all-to-all", "reduce-scatter"):
            assert op not in text, (k, op)
