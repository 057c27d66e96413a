"""``BENCHMARK.json`` and its loader: every part is found by its name, the
file keeps the benchmark's contract, and names or units with other
characters are refused."""

from __future__ import annotations

import copy
import json
import shutil

import pytest

from bench.manifest import BENCH_DIR, Manifest, ManifestError
from perfbench_tiny import REPO

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def manifest():
    return Manifest(MANIFEST)


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert MANIFEST["paths"] == ["bench"]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_part_of_a_cell_is_found_by_name(manifest, cell):
    c = manifest.cell(cell)
    cfg = manifest.config_json(c)
    assert cfg["name"] == c.config["name"]
    assert set(cfg["reduced"]) == set(c.config["reduced"])
    for key in ("source", "published", "reduced", "assumed", "departures", "precision"):
        assert cfg[key] or key == "reduced", key
    assert hasattr(manifest.builder(c), "build")
    ref = manifest.reference(c)
    assert hasattr(ref, "forward") and hasattr(ref, "init_weights")
    assert manifest.traffic(c)["loop"] == "closed"
    assert 0 < cfg["check_limit"] < 1
    for m in c.end_to_end + c.per_layer:
        assert hasattr(manifest.metric_reader(m), "read")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    # a per-layer metric's cells all report the end-to-end metric it moves
    assert all(m["moves"] in names for m in c.per_layer)


def test_entries_hold_only_the_contract_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("field,value", [
    ("name", "bad name"), ("name", "slash/name"), ("unit", "tokens per second"),
    ("unit", "µs"), ("better", "faster"), ("source", "stopwatch"),
])
def test_bad_metric_entries_are_refused(field, value):
    data = copy.deepcopy(MANIFEST)
    data["end_to_end"][0][field] = value
    with pytest.raises(ManifestError):
        Manifest(data)


def test_a_cell_with_an_unknown_config_or_a_repeated_pair_is_refused():
    data = copy.deepcopy(MANIFEST)
    data["workloads"].append(dict(data["workloads"][0], name="another"))
    with pytest.raises(ManifestError):
        Manifest(data)
    data = copy.deepcopy(MANIFEST)
    data["workloads"][0]["config"] = "no-such-model"
    with pytest.raises(ManifestError):
        Manifest(data)


def test_parts_are_added_by_files_and_entries_alone(tmp_path):
    """A new traffic mix, metric and cell: new files and manifest entries,
    no existing file edited."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = json.loads((bench / "traffic/sat.json").read_text())
    mix.update(clients=16, prompt_len=4096)
    (bench / "traffic/long.json").write_text(json.dumps(mix))
    (bench / "metrics/attempted_count.py").write_text(
        "def read(run):\n    return len(run.due_in_window())\n")
    data = copy.deepcopy(MANIFEST)
    data["workloads"].append({"name": "mamba2-2.7b.long", "config": "mamba2-2.7b",
                              "traffic": "long", "chips": 1, "why": "longer prompts"})
    for m in data["end_to_end"] + data["per_layer"]:
        if "workloads" in m and m["name"] in ("served_rps", "mfu.sat"):
            m["workloads"].append("mamba2-2.7b.long")
    data["per_layer"].append({"name": "attempted_count", "unit": "req", "better": "higher",
                              "source": "host_clock", "layer": "harness",
                              "moves": "served_rps", "workloads": ["mamba2-2.7b.long"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    m = Manifest.load(tmp_path / "BENCHMARK.json", bench)
    cell = m.cell("mamba2-2.7b.long")
    assert m.traffic(cell)["prompt_len"] == 4096
    assert [x["name"] for x in cell.per_layer] == ["mfu.sat", "deploy_ms", "attempted_count"]
    assert {x["name"] for x in cell.end_to_end} == {"served_rps", "setup_s"}
    for x in cell.end_to_end + cell.per_layer:
        assert hasattr(m.metric_reader(x), "read")
    reader = m.metric_reader(cell.per_layer[-1])

    class FakeRun:
        def due_in_window(self):
            return [1, 2, 3]

    assert reader.read(FakeRun()) == 3
