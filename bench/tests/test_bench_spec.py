"""``BENCHMARK.json`` and the files it names keep the benchmark's rules:
names and units, one file per configuration, mix, cell and metric, each
metric moving an end-to-end metric its cells report, and the peak table."""

import json
import re

import pytest

from bench_cpu_sizes import ROOT
from bench import peaks

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_has_its_file():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert (ROOT / "bench/reference" / f"{conf['reference']}.py").exists()
    for w in BENCH["workloads"]:
        assert w["chips"] == 1
        assert (ROOT / "bench/cells" / f"{w['name']}.json").exists()
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").exists()
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists(), m


def test_metric_entries():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= METRIC_KEYS | {"bound", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= METRIC_KEYS | {"layer", "moves", "workloads"}
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", sorted(cells)):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]


def test_every_cell_reports_setup_another_and_a_layer():
    for w in BENCH["workloads"]:
        def has(m):
            return "workloads" not in m or w["name"] in m["workloads"]
        e2e = [m["name"] for m in BENCH["end_to_end"] if has(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(has(m) for m in BENCH["per_layer"])


def test_peak_table():
    v5e = peaks.for_device("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    for kind in peaks.PEAKS:
        assert NAME.match(kind.replace(" ", "_"))
    with pytest.raises(KeyError):
        peaks.for_device("TPU v9 imaginary")


def test_cell_files_hold_numbers():
    for w in BENCH["workloads"]:
        cell = json.loads((ROOT / "bench/cells" / f"{w['name']}.json")
                          .read_text())
        assert cell["rate"] > 0 and cell["gap_limit"] > 0
        assert cell["lead_in_s"] >= 0 and cell["check_tokens"] > 0
