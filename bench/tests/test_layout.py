"""``BENCHMARK.json`` and the files it names hold together."""

import json
import math

import pytest

import run
import work
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads(cell):
    spec = run.load_cell(cell)
    names = {m["name"] for m in spec["end_to_end"]}
    assert {"setup_s", "steps_per_s"} <= names
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        assert m["moves"] in names
        base = m["name"].split(".")[0]
        assert (BENCH / "metrics" / f"{base}.py").is_file()
    cfg = spec["cfg"]
    assert set(cfg["check"]) == {"max_abs_err", "rms_err"}
    assert spec["traffic"]["pool"]["slots"] >= 1


def _empty_ctx():
    return {"cfg": json.loads((BENCH / "configs" / "esn1024_int8.json")
                              .read_text()),
            "chips": 1, "device_kind": "TPU v5 lite",
            "work": work.Work(1, 128, 1, 1, 1), "live_steps": 0,
            "total_steps": 0, "launches": 0, "rows_per_chip": 8,
            "chunk_steps": 16, "step_s": [], "dispatch_s": [],
            "trace": None}


@pytest.mark.parametrize("metric", sorted({m["name"]
                                           for m in SPEC["per_layer"]}))
def test_reader_with_nothing_to_read_returns_nothing(metric):
    assert run.read_metric(metric, _empty_ctx()) is None


def test_roofline_and_mfu_from_the_work_table():
    ctx = _empty_ctx()
    w = ctx["work"]
    ctx.update(launches=10, live_steps=100, step_s=[0.5, 0.5],
               trace={"window_s": 1.0, "busy_s": 0.25, "ops": {},
                      "programs": {"jit_launch": 2.0, "jit_other": 5.0},
                      "idle": {}})
    bound = 10 * work.bound_s(w, 8, 16, "TPU v5 lite", ctx["cfg"])
    assert run.read_metric("kernel_roofline.thru", ctx) == pytest.approx(
        100 * bound / 2.0)
    assert run.read_metric("mfu.thru", ctx) == pytest.approx(
        100 * 100 * w.ops_per_row_step / (1.0 * 393e12))
    assert run.read_metric("device_idle.tail", ctx) == pytest.approx(75.0)


def test_result_line_stays_json():
    out = run.finite({"a": math.inf, "b": [1.0, math.nan], "c": 2})
    assert out == {"a": None, "b": [1.0, None], "c": 2}
    json.loads(json.dumps(out, allow_nan=False))
