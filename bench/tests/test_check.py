"""The check that decides ``correct``: it passes the program, and fails its
lower-precision control and a timed path broken underneath.

Each test drives the rest of a run (``run.run_cell``: set-up, warm-up,
window, check) on the CPU at a small width, past the harness's look for a
chip, with the configuration's own limits.
"""

import jax.numpy as jnp
import pytest

import model
import run
import traffic
from conftest import small_cfg, small_spec
from repro.dist import ShardedContinuousBatcher
from repro.serve.engine import ReservoirEngine

SECONDS = 0.5
SEED = 2**31 + 3


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_sound_run_is_correct(loop, cpu_devices):
    out = run.run_cell(small_spec(loop), SEED, SECONDS, False, cpu_devices)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = list(out["metrics"])
    assert "setup_s" in names and "steps_per_s" in names
    assert ("latency_p95_ms" in names) == (loop == "open")
    assert list(out)[-1] == "checks"


def _state_unchanged(orig):
    def run_segment(self, inputs, x0, **kw):
        keep = jnp.array(x0)
        out, _xf = orig(self, inputs, x0, **kw)
        return out, keep
    return run_segment


def _half_batch(orig):
    # the first half of the pool's rows (where a lightly loaded pool
    # seats its requests) left out: no output, state unchanged
    def run_segment(self, inputs, x0, **kw):
        h = inputs.shape[0] // 2
        out, xf = orig(self, inputs[h:], x0[h:], **kw)
        pad = jnp.zeros((h,) + out.shape[1:], out.dtype)
        return (jnp.concatenate([pad, out]),
                jnp.concatenate([jnp.asarray(x0)[:h], xf]))
    return run_segment


def _answer_altered(orig):
    def run_segment(self, inputs, x0, **kw):
        out, xf = orig(self, inputs, x0, **kw)
        # each chunk's last prediction repeats the one before it
        return out.at[:, -1].set(out[:, -2]), xf
    return run_segment


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered"])
@pytest.mark.parametrize("loop", ["closed", "open"])
def test_broken_timed_path_is_not_correct(fault, loop, cpu_devices,
                                          monkeypatch):
    monkeypatch.setattr(ReservoirEngine, "run_segment",
                        fault(ReservoirEngine.run_segment))
    out = run.run_cell(small_spec(loop), SEED, SECONDS, False, cpu_devices)
    assert not out["correct"], out["checks"]


def _zero_copy(init):
    # the sharded pool as a TPU holds it by default: inputs resident on the
    # shards, each chunk gathered there (the CPU's default copies them in)
    def zero_copy_init(self, *a, **kw):
        init(self, *a, **(kw | {"zero_copy": True}))
    return zero_copy_init


def _exchange_left_out(init):
    # the chunk gather's exchange between chips (an all-gather of the lane
    # indices) left out: every shard gathers with the first shard's block
    def init_without_exchange(self, *a, **kw):
        init(self, *a, **kw)
        gather, per, n = self._gather, self.slots_per_shard, self.n_shards
        self._gather = lambda u_dev, idx: gather(u_dev,
                                                 jnp.tile(idx[:per], n))
    return init_without_exchange


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch,
                                   _answer_altered, "exchange"],
                         ids=["sound", "state-unchanged", "half-batch",
                              "answer-altered", "exchange-left-out"])
def test_four_chip_path(fault, cpu_devices_x4, monkeypatch):
    """The four-chip cell's path (DistributedReservoirServer over four CPU
    devices): correct when sound, not correct under each fault."""
    init = _zero_copy(ShardedContinuousBatcher.__init__)
    if fault == "exchange":
        init = _exchange_left_out(init)
    elif fault is not None:
        monkeypatch.setattr(ReservoirEngine, "run_segment",
                            fault(ReservoirEngine.run_segment))
    monkeypatch.setattr(ShardedContinuousBatcher, "__init__", init)
    out = run.run_cell(small_spec("closed", chips=4), SEED, SECONDS, False,
                       cpu_devices_x4)
    assert out["correct"] == (fault is None), out["checks"]
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("name", list(model.CONTROLS))
def test_lower_precision_control_fails(name):
    """The reference computed one precision step below the configuration,
    in the program's place, fails at least one of the check's limits."""
    cfg = small_cfg("esn1024_int8", dim=256)
    weights = model.make_weights(cfg)
    mix = traffic.Traffic({"loop": "closed", "lengths": {
        "dist": "uniform", "min": 64, "max": 512}}, SEED, 1)
    inputs = [mix.inputs(k) for k in range(16)]
    refs = model.reference_preds(cfg, weights, inputs)
    ctrl = model.reference_preds(cfg, weights, inputs, **model.CONTROLS[name])
    got = run.compare(ctrl, refs)
    assert (got["max_abs_err"] > cfg["check"]["max_abs_err"]
            or got["rms_err"] > cfg["check"]["rms_err"]), got
