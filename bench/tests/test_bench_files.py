"""BENCHMARK.json and the files it names: every cell resolves its
configuration, traffic, entry, reference and metric readers by name, and
the file keeps to the benchmark's contract."""

import json
import os
import re

import pytest

from bench import harness, work

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_file(name):
    cell = harness.load_cell(name)
    for fn in ("build", "call", "fetch"):
        assert callable(getattr(cell.entry, fn))
    assert callable(cell.reference.run_call)
    e2e = [m["name"] for m, _ in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for _, reader in cell.end_to_end + cell.per_layer:
        assert callable(reader.read)
    assert set(cell.config["limits"]) == {"count_mismatch", "state_gap_mV"}
    assert cell.traffic["lanes"] >= 1 and cell.traffic["steps"] >= 1


def test_names_units_and_bounds():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.fullmatch(e["name"]), e["name"]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m["workloads"]:
            assert w in CELLS
            reporting = [e for e in BENCH["end_to_end"]
                         if e["name"] == m["moves"]][0]
            assert w in reporting.get("workloads", CELLS)


def test_configs_and_cells():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == []
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def test_peaks_known_kind():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["ops_per_s"] == 197e12


def test_peaks_unknown_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v99 imaginary")


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no_such_cell")
