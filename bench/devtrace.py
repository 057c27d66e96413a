"""From the profiler's trace to the numbers the per-layer metrics read.

A traced run writes one ``.xplane.pb``.  ``events_from_xplane`` takes three
kinds of events out of it, as plain dicts ``{"name", "start_ns", "dur_ns"}``:

- ``ops``: the device's ``XLA Ops`` line, one event per HLO op executed;
  the name is the op's HLO text, output and operand shapes included;
- ``modules``: the device's ``XLA Modules`` line, one event per program run;
- ``host``: the benchmark's own ``TraceAnnotation`` spans (``bench.*``).

``DeviceTrace`` reduces them: the device's busy time (union of op intervals
inside the measured window, which the ``bench.window`` span marks), the
kernels' events with their shapes, the programs that took most device time,
and the longest idle gaps with what the harness was doing during each.
Times on both planes are on the profiler's one clock.
"""

from __future__ import annotations

import glob
import json
import re
from dataclasses import dataclass
from pathlib import Path

WINDOW_SPAN = "bench.window"
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_HASH = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"[\]\})]\s+[a-z][\w\-]*\(")


def events_from_xplane(path: str | Path, n_devices: int = 1) -> dict:
    """Device ops and modules of the first ``n_devices`` TPU planes, and the
    harness's host spans, as JSON-ready lists."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = {"ops": [], "modules": [], "host": [], "devices": 0}
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_devices:
            out["devices"] += 1
            dev = int(m.group(1))
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    out[key].append({"name": e.name, "start_ns": float(e.start_ns),
                                     "dur_ns": float(e.duration_ns), "device": dev})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append({"name": e.name,
                                            "start_ns": float(e.start_ns),
                                            "dur_ns": float(e.duration_ns)})
    return out


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins/profile/*/*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def hlo_shapes(text: str) -> tuple[list[tuple[str, tuple[int, ...]]], list[tuple[str, tuple[int, ...]]]]:
    """(output shapes, operand shapes) of one HLO op's text, each a list of
    ``(dtype, dims)``; layout annotations are ignored."""
    _, _, rest = text.partition("=")
    call = _OPCODE.search(rest)  # the opcode follows the output shape
    if call is None:
        return [], []
    out_txt = rest[:call.start()]
    body = rest[call.end():]
    depth, end = 1, 0
    for i, ch in enumerate(body):  # operands end at the matching parenthesis
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    def shapes(s):
        return [(dt, tuple(int(x) for x in dims.split(",") if x))
                for dt, dims in _SHAPE.findall(s)]
    return shapes(out_txt), shapes(body[:end])


def op_name(text: str) -> str:
    """``%ssd_chunked.1 = f32[...] custom-call(...)`` -> ``ssd_chunked``."""
    head = text.split("=", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def is_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclass
class DeviceTrace:
    ops: list[dict]
    modules: list[dict]
    host: list[dict]
    devices: int

    @classmethod
    def from_events(cls, events: dict) -> "DeviceTrace":
        return cls(events["ops"], events["modules"], events["host"],
                   max(1, int(events.get("devices", 1))))

    @classmethod
    def load_json(cls, path: str | Path) -> "DeviceTrace":
        return cls.from_events(json.loads(Path(path).read_text()))

    # -- the window ----------------------------------------------------------
    @property
    def window_ns(self) -> tuple[float, float]:
        spans = [h for h in self.host if h["name"] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN} span")
        w = spans[-1]
        return w["start_ns"], w["start_ns"] + w["dur_ns"]

    @property
    def window_s(self) -> float:
        a, b = self.window_ns
        return (b - a) * 1e-9

    def _clipped(self, events, device=None):
        a, b = self.window_ns
        for e in events:
            if device is not None and e.get("device", 0) != device:
                continue
            s, t = max(e["start_ns"], a), min(e["start_ns"] + e["dur_ns"], b)
            if t > s:
                yield e, s, t

    def busy_intervals(self, device: int = 0) -> list[tuple[float, float]]:
        return _union([(s, t) for _, s, t in self._clipped(self.ops, device)])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran on the device, averaged over chips."""
        total = 0.0
        for dev in range(self.devices):
            total += sum(t - s for s, t in self.busy_intervals(dev))
        return total * 1e-9 / self.devices

    # -- kernels ---------------------------------------------------------------
    def kernel_ops(self, match) -> list[dict]:
        """Kernel events inside the window that ``match(name, outs, ins)``
        accepts, each with its shapes and device seconds."""
        found = []
        for e, s, t in self._clipped(self.ops):
            if not is_kernel(e["name"]):
                continue
            outs, ins = hlo_shapes(e["name"])
            name = op_name(e["name"])
            if match(name, outs, ins):
                found.append({"name": name, "outs": outs, "ins": ins,
                              "seconds": (t - s) * 1e-9})
        return found

    # -- breakdown -------------------------------------------------------------
    def top_programs(self, n: int = 10) -> list[list]:
        """The device programs that took most time in the window."""
        acc: dict[str, float] = {}
        for e, s, t in self._clipped(self.modules, 0):
            key = _HASH.sub("", e["name"])
            acc[key] = acc.get(key, 0.0) + (t - s) * 1e-9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest idle stretches of chip 0 in the window, each named by
        the harness span that was open at its midpoint (the innermost one)."""
        a, b = self.window_ns
        busy = self.busy_intervals(0)
        gaps, cur = [], a
        for s, t in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, t)
        if b > cur:
            gaps.append((cur, b))
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        spans = [h for h in self.host if h["name"] != WINDOW_SPAN]
        out = []
        for s, t in gaps[:n]:
            mid = 0.5 * (s + t)
            open_ = [h for h in spans if h["start_ns"] <= mid <= h["start_ns"] + h["dur_ns"]]
            label = (max(open_, key=lambda h: h["start_ns"])["name"]
                     if open_ else "bench.outside")
            out.append([label.removeprefix("bench."), (t - s) * 1e-9])
        return out
