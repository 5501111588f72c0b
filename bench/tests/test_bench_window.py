"""The harness's whole run on the CPU at a small size, through
``harness.run_cell`` (``bench/run.py`` itself refuses without a TPU):
the open-loop window, the metrics each cell reports and the result
line's schema, and the comparison with the reference."""

import json
import time

import pytest

from bench_cpu_sizes import CELLS, ROOT, SMALL
from bench import harness

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(cell, seed, trace, seconds=3.0):
    sp = harness.load_spec(cell, overrides=SMALL)
    return sp, harness.run_cell(sp, seed, seconds, trace,
                                t_start=time.perf_counter(),
                                log=lambda m: None)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_with_its_metrics(cell):
    sp, out = _run(cell, 2 ** 33 + 5, trace=False)
    assert list(out)[:5] == RESULT_KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in sp.end_to_end}
    for m in sp.end_to_end:
        v = out["metrics"][m["name"]]
        assert v["unit"] == m["unit"] and v["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["bad_lengths"]["value"] == 0
    json.dumps(out)


def test_traced_run_reports_per_layer_and_breakdown():
    cell = CELLS[0]
    sp, out = _run(cell, 17, trace=True)
    assert out["correct"] is True, out["checks"]
    names = {m["name"] for m in sp.per_layer}
    # on the CPU only the host-side readers find something to read;
    # device metrics (trace of a chip, peaks) stay out of the line
    assert {"gen_lag_p99_ms", "admit_ms_per_req",
            "exchange_kib_per_step"} <= set(out["metrics"]) <= names
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0
    assert list(out)[-1] == "checks"


def test_spec_lists_each_cells_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        sp = harness.load_spec(w["name"])
        e2e = {m["name"] for m in sp.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert sp.per_layer
        for m in sp.per_layer:
            assert m["moves"] in e2e
