"""``BENCHMARK.json``: load it, check its names, and find each part's files.

Everything that belongs to one configuration, one traffic mix or one metric
lives in files of its own, found by the name the manifest gives it:

    bench/configs/<config>.json      sizes as run, source, reduced, assumed
    bench/configs/<config>.build.py  the model composed from the program's blocks
    bench/configs/<config>.ref.py    the plain float32 reference and the weights
    bench/traffic/<traffic>.json     one traffic mix, read by ``traffic.py``
    bench/metrics/<metric>.py        one metric's reader, ``read(run)``

A later change adds a configuration, a mix or a metric by adding such files
and a manifest entry; no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(ValueError):
    """``BENCHMARK.json`` breaks a rule, or names a file that is not there."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"{what} {name!r}: a name is 1-64 of A-Z a-z 0-9 _ . - "
                            f"and starts with a letter, digit or _")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(f"{what}: unit {unit!r} is not 1-16 of "
                            f"A-Z a-z 0-9 _ / % . -")
    return unit


def load_module(path: Path, prefix: str):
    """Import a file of the benchmark by its path (names may hold . and -)."""
    mod_name = prefix + re.sub(r"\W", "_", path.stem)
    if mod_name in sys.modules and getattr(sys.modules[mod_name], "__file__", None) == str(path):
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ManifestError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict  # the manifest's configs entry
    traffic_name: str
    chips: int
    end_to_end: tuple[dict, ...]  # metrics this cell reports with --trace 0
    per_layer: tuple[dict, ...]  # metrics this cell reports with --trace 1


class Manifest:
    def __init__(self, data: dict, bench_dir: Path = BENCH_DIR):
        self.data = data
        self.bench_dir = Path(bench_dir)
        self._validate()

    @classmethod
    def load(cls, path: Path, bench_dir: Path = BENCH_DIR) -> "Manifest":
        try:
            data = json.loads(Path(path).read_text())
        except (OSError, json.JSONDecodeError) as e:
            raise ManifestError(f"cannot read {path}: {e}") from None
        return cls(data, bench_dir)

    # -- rules -------------------------------------------------------------
    def _validate(self) -> None:
        d = self.data
        configs = {check_name(c["name"], "config"): c for c in d["configs"]}
        if len(configs) != len(d["configs"]):
            raise ManifestError("two configs share a name")
        cells, pairs = set(), set()
        for w in d["workloads"]:
            check_name(w["name"], "workload")
            check_name(w["traffic"], "traffic")
            if w["config"] not in configs:
                raise ManifestError(f"workload {w['name']}: unknown config {w['config']!r}")
            if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"workload {w['name']} repeats a name or a pair")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"workload {w['name']}: chips must be 1 or 4")
            cells.add(w["name"])
            pairs.add((w["config"], w["traffic"]))
        seen = set()
        for kind in ("end_to_end", "per_layer"):
            for m in d[kind]:
                check_name(m["name"], "metric")
                check_unit(m["unit"], m["name"])
                if m["name"] in seen:
                    raise ManifestError(f"metric {m['name']} appears twice")
                seen.add(m["name"])
                if m["better"] not in ("lower", "higher"):
                    raise ManifestError(f"metric {m['name']}: better is lower or higher")
                if m["source"] not in SOURCES:
                    raise ManifestError(f"metric {m['name']}: unknown source {m['source']!r}")
                for cell in m.get("workloads", ()):
                    if cell not in cells:
                        raise ManifestError(f"metric {m['name']}: unknown workload {cell!r}")
        names = {m["name"] for m in d["end_to_end"]}
        if "setup_s" not in names:
            raise ManifestError("end_to_end must hold setup_s")
        for m in d["per_layer"]:
            if m["moves"] not in names:
                raise ManifestError(f"metric {m['name']} moves unknown {m['moves']!r}")

    # -- lookup ------------------------------------------------------------
    def cell(self, name: str) -> Cell:
        for w in self.data["workloads"]:
            if w["name"] == name:
                break
        else:
            raise ManifestError(f"no workload named {name!r}")
        config = next(c for c in self.data["configs"] if c["name"] == w["config"])

        def reports(m):
            return name in m.get("workloads", [name])

        return Cell(
            name=name, config=config, traffic_name=w["traffic"], chips=w["chips"],
            end_to_end=tuple(m for m in self.data["end_to_end"] if reports(m)),
            per_layer=tuple(m for m in self.data["per_layer"] if reports(m)),
        )

    def _file(self, rel: str) -> Path:
        path = self.bench_dir / rel
        if not path.is_file():
            raise ManifestError(f"missing file {path}")
        return path

    def config_json(self, cell: Cell) -> dict:
        # ``file`` is relative to the repo root and lies under bench/
        rel = Path(cell.config["file"])
        path = self.bench_dir.parent / rel
        if not path.is_file():
            raise ManifestError(f"missing file {path}")
        return json.loads(path.read_text())

    def builder(self, cell: Cell):
        return load_module(self._file(f"configs/{cell.config['name']}.build.py"),
                           "bench_build_")

    def reference(self, cell: Cell):
        return load_module(self._file(f"configs/{cell.config['name']}.ref.py"),
                           "bench_ref_")

    def traffic(self, cell: Cell) -> dict:
        return json.loads(self._file(f"traffic/{cell.traffic_name}.json").read_text())

    def metric_reader(self, metric: dict):
        return load_module(self._file(f"metrics/{metric['name']}.py"), "bench_metric_")
