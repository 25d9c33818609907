"""BENCHMARK.json and the files it names: the contract's shape, names and
units, and that every configuration, traffic mix and metric is found by
name."""

import json
import re

import harness
import pytest

SPEC = harness.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in SPEC["configs"]] + CELLS
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in (CELLS, [c["name"] for c in SPEC["configs"]],
                  [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]):
        assert len(group) == len(set(group))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in [c["why"] for c in SPEC["configs"]] + [
        w["why"] for w in SPEC["workloads"]
    ] + [m["layer"] for m in SPEC["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_metrics_and_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
        assert not m["unit"] == "%" or m["name"].endswith(
            ("_roofline", "_pct", ".grid", ".replay")
        )


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files_by_name(name):
    cell = harness.bench_run.Cell(SPEC, name)
    assert cell.entry["chips"] == 1
    assert hasattr(cell.driver_module, "Driver")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    readers = cell.readers()
    assert set(readers) == {m["name"] for m in cell.per_layer}
    assert all(callable(r.read) for r in readers.values())
    assert set(cell.traffic["limits"])


def test_configuration_files_are_distinct_and_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        data = json.loads((harness.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert set(c["reduced"]) <= set(data)
        assert data["assumed"]


def test_peaks_of_the_chip_and_an_unknown_device():
    peaks = harness.bench_run.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.bench_run.BenchError):
        harness.bench_run.peaks_for("TPU v9 imaginary")
