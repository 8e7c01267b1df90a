"""BENCHMARK.json against the benchmark contract, and discovery by name.

Every name, unit and text field is checked for the characters and lengths
the contract allows; every configuration, traffic mix, metric reader and
cell limit file the spec names has to exist where the harness looks.
"""
import json
import re

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_text(w) for w in cmd)
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in SPEC["paths"])
            assert (ROOT / w).is_file()
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_check_budget_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert _text(entry["source"]) and _text(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in SPEC["paths"])
    cfg = harness.config(SPEC, entry["name"])
    assert cfg["name"] == entry["name"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg["reduced"]
        assert not re.search(r"(_dim|_rank|width|height|size)$", key)
    assert (ROOT / cfg["reference"]).is_file()
    others = [e["file"] for e in SPEC["configs"] if e is not entry]
    assert entry["file"] not in others
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and _text(cell["why"])
    harness.config(SPEC, cell["config"])
    mix = harness.traffic(cell["traffic"])
    assert mix["loop"] in ("closed", "open")
    lims = harness.limits(cell["name"])
    from bench import check
    assert set(lims) == set(check.NUMBERS)
    assert all(v > 0 for v in lims.values())
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1
    reported = harness.cell_metrics(SPEC, cell["name"], False)
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert harness.cell_metrics(SPEC, cell["name"], True)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(metric):
    e2e = metric in SPEC["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert _text(metric["layer"])
        moved = [m for m in SPEC["end_to_end"] if m["name"] ==
                 metric["moves"]]
        assert moved
        for cell in metric.get("workloads", []):
            assert cell in moved[0].get("workloads", [cell])
    for cell in metric.get("workloads", []):
        harness.workload(SPEC, cell)
    assert callable(harness.reader(metric["name"]))


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


def test_four_chip_cells_within_share():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_a_new_metric_is_found_by_name(tmp_path):
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "frames_twice.py").write_text(
        "def read(run):\n    return 2 * run\n")
    assert harness.reader("frames_twice", root=tmp_path)(21) == 42


def test_a_new_cell_is_found_by_name(tmp_path):
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0], name="tiny",
                                file="bench/configs/tiny.json"))
    spec["workloads"].append(dict(spec["workloads"][0], name="tiny.orbit",
                                  config="tiny", traffic="orbit"))
    for d in ("configs", "traffic", "checks"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps({"name": "tiny", "width": 32}))
    (tmp_path / "bench" / "traffic" / "orbit.json").write_text(
        json.dumps({"loop": "closed", "in_flight": 1,
                    "poses": {"kind": "uniform"}}))
    (tmp_path / "bench" / "checks" / "tiny.orbit.json").write_text(
        json.dumps({"limits": {"image_mae": 1.0}}))
    loaded = harness.load_spec(tmp_path)
    cell = harness.workload(loaded, "tiny.orbit")
    assert harness.config(loaded, cell["config"], tmp_path)["width"] == 32
    assert harness.traffic(cell["traffic"], tmp_path)["in_flight"] == 1
    assert harness.limits("tiny.orbit", tmp_path) == {"image_mae": 1.0}

