"""The program's spans (``seifer.*``) reduced to the per-layer metrics that
read them: exact idle attribution on a hand-built trace, the readers on a
trace recorded on a TPU v5 lite chip, the device-only sample's reductions
left as they were, the spans found again in a run's ``.xplane.pb``, and a
traced tiny run on the CPU end to end."""

from __future__ import annotations

import json
import math
from types import SimpleNamespace
from unittest import mock

import jax
import pytest

from bench import devtrace, spans
from bench.devtrace import DeviceTrace
from bench.harness import run_cell
from bench.manifest import Manifest, load_module
from perfbench_tiny import BENCH, TINY_CELLS, tiny_bench

READERS = ("stage_call_ms", "engine_self_ms", "idle_in_dispatch.sat",
           "idle_in_engine.sat", "idle_in_gc.sat")
IDLE_READERS = READERS[2:]
DEVICE_SAMPLE = BENCH / "tests/data/v5e_trace_sample.json"
PROGRAM_SAMPLE = BENCH / "tests/data/v5e_program_trace_sample.json"
U = 1000.0  # the hand-built trace's unit: 1 us


def reader(metric: str):
    return load_module(BENCH / f"metrics/{metric}.py", "test_metric_")


def read(metric: str, trace, program):
    """``metric``'s reader on ``trace``, with ``program`` as the spans its
    run's ``.xplane.pb`` held."""
    with mock.patch.object(spans, "program_spans", lambda run, results: program):
        return reader(metric).read(SimpleNamespace(device=trace))


def _span(name, a, b, thread="main", **args):
    return {"name": name, "start_ns": a * U, "dur_ns": (b - a) * U,
            "thread": thread, "args": args}


def _hand_built() -> tuple[DeviceTrace, list[dict]]:
    """Chip 0 idle over [100,150], [300,320], [400,600], [700,900] and
    [950,1000] of a [0,1000] window, under two steps:

    step A [90,480]: admit [90,110], stage 0 [110,200], codec [200,310],
    stage 1 [310,450] holding a collection [420,440];
    step B [560,980]: complete [700,760], stage 0 [760,880], a collection
    [890,920] in the step itself.  Nothing is open over [480,560] and
    [980,1000].  A step before the window and a collection on another
    thread are not read."""
    busy = [(0, 100), (150, 300), (320, 400), (600, 700), (900, 950)]
    trace = DeviceTrace.from_events({
        "ops": [{"name": "op", "start_ns": a * U, "dur_ns": (b - a) * U, "device": 0}
                for a, b in busy],
        "modules": [],
        "host": [{"name": "bench.window", "start_ns": 0.0, "dur_ns": 1000 * U},
                 {"name": "bench.step", "start_ns": 90 * U, "dur_ns": 390 * U}],
        "devices": 1,
    })
    return trace, [
        _span("seifer.step", -50, -10),
        _span("seifer.step", 90, 480),
        _span("seifer.admit", 90, 110, batch=8),
        _span("seifer.stage", 110, 200, stage=0, first=0, stop=32, batch=8),
        _span("seifer.codec", 200, 310, hop=1, codec="int8", op="transcode"),
        _span("seifer.stage", 310, 450, stage=1, first=32, stop=64, batch=8),
        _span("seifer.gc", 420, 440, generation=0),
        _span("seifer.gc", 480, 600, thread="other", generation=2),
        _span("seifer.step", 560, 980),
        _span("seifer.complete", 700, 760, batch=8),
        _span("seifer.stage", 760, 880, stage=0, first=0, stop=32, batch=8),
        _span("seifer.gc", 890, 920, generation=1),
    ]


def test_idle_goes_exactly_to_the_innermost_open_span():
    trace, program = _hand_built()
    by = spans.idle_by_span(trace, program)
    # [100,150] crosses admit -> stage 0; [300,320] codec -> stage 1;
    # [400,600] stage 1, the collection in it, stage 1, step A, nothing
    # (80), step B; [700,900] complete, stage 0, step B, the collection;
    # [950,1000] step B, nothing (20)
    assert by == {"seifer.admit": 10 * U, "seifer.stage": (40 + 10 + 20 + 10 + 120) * U,
                  "seifer.codec": 10 * U, "seifer.gc": (20 + 10) * U,
                  "seifer.step": (30 + 40 + 10 + 30) * U, "seifer.complete": 60 * U}
    idle = sum(b - a for a, b in spans.idle_intervals(trace))
    assert idle == 520 * U
    assert idle - sum(by.values()) == (80 + 20) * U  # left unattributed


def test_innermost_stretches_tile_the_open_spans():
    found = spans.window_spans(*_hand_built())
    segs = spans.innermost(found)
    assert [(a / U, b / U, s["name"]) for a, b, s in segs[:6]] == [
        (90, 110, "seifer.admit"), (110, 200, "seifer.stage"),
        (200, 310, "seifer.codec"), (310, 420, "seifer.stage"),
        (420, 440, "seifer.gc"), (440, 450, "seifer.stage")]
    assert all(b > a for a, b, _ in segs)
    assert all(s1[1] <= s2[0] for s1, s2 in zip(segs, segs[1:]))


@pytest.mark.parametrize("metric,want", [
    ("stage_call_ms", (90 + 140 + 120) / 3 * U * 1e-6),
    # step A 390 less stage, codec, stage [110,450]; step B 420 less 120 + 30
    ("engine_self_ms", ((390 - 340) + (420 - 150)) / 2 * U * 1e-6),
    ("idle_in_dispatch.sat", 100.0 * (40 + 10 + 10 + 20 + 10 + 120) / 1000),
    ("idle_in_engine.sat", 100.0 * (10 + 60 + 30 + 40 + 10 + 30) / 1000),
    ("idle_in_gc.sat", 100.0 * (20 + 10) / 1000),
])
def test_readers_give_the_hand_counted_values(metric, want):
    assert read(metric, *_hand_built()) == pytest.approx(want, rel=1e-12)


def test_self_time_reads_the_outermost_step_only():
    nested = [_span("seifer.step", 0, 100), _span("seifer.step", 10, 90),
              _span("seifer.stage", 20, 50), _span("seifer.step", 200, 260)]
    assert [s["start_ns"] for s in spans.outermost(nested, "seifer.step")] == [0, 200 * U]
    assert spans.self_ms(nested, "seifer.step", spans.DISPATCH) == pytest.approx(
        [70 * U * 1e-6, 60 * U * 1e-6])


def test_stage_calls_keep_their_metadata():
    found = [s for s in spans.window_spans(*_hand_built()) if s["name"] == spans.STAGE]
    assert [s["args"]["stage"] for s in found] == [0, 1, 0]
    assert spans.durations_ms(found, spans.STAGE) == pytest.approx([0.09, 0.14, 0.12])


@pytest.fixture(scope="module")
def chip_trace():
    """The chip sample's trace and program spans."""
    return (DeviceTrace.load_json(PROGRAM_SAMPLE),
            json.loads(PROGRAM_SAMPLE.read_text())["program"])


@pytest.mark.parametrize("metric", READERS)
def test_readers_are_finite_on_the_chip_sample(chip_trace, metric):
    value = read(metric, *chip_trace)
    assert value is not None and math.isfinite(value) and value >= 0


def test_idle_buckets_stay_within_the_idle_share_on_the_chip_sample(chip_trace):
    idle = reader("idle_share.sat").read(SimpleNamespace(device=chip_trace[0]))
    buckets = [read(m, *chip_trace) for m in IDLE_READERS]
    assert 0 < sum(buckets) <= idle + 1e-9
    assert read("stage_call_ms", *chip_trace) > 0


def test_chip_sample_holds_each_stage_of_each_batch(chip_trace):
    found = spans.window_spans(*chip_trace)
    stages = [s["args"]["stage"] for s in found if s["name"] == spans.STAGE]
    counts = {stages.count(i) for i in set(stages)}
    assert len(set(stages)) >= 2 and len(counts) == 1  # every stage, every batch


def test_program_sample_file_is_small_and_plain_json():
    data = json.loads(PROGRAM_SAMPLE.read_text())
    assert PROGRAM_SAMPLE.stat().st_size < 128 * 1024
    assert {"ops", "modules", "host", "program"} <= set(data)


# -- the device-only sample: its reductions as before, and no program spans ----

@pytest.fixture(scope="module")
def device_only():
    return DeviceTrace.load_json(DEVICE_SAMPLE)


# what the breakdown's code gave on this sample before program spans were
# kept, as a result line prints it
DEVICE_BUSY_S, DEVICE_WINDOW_S = 0.012153485, 0.482683006
DEVICE_TOP = (
    '[["jit_ssd_chunked", 0.005462112000000001], ["jit__einsum", 0.002402557], '
    '["jit_wrapped", 0.002097856], ["jit_multiply", 0.000528233], '
    '["jit_silu", 0.0005276160000000001], ["jit_reshape", 0.000460244], '
    '["jit_quantize_int8", 0.000285942], ["jit__moveaxis", 0.000257314], '
    '["jit_dequantize_int8", 0.000132356]]')
DEVICE_GAPS = (
    '[["flash", 0.46939229200000004], ["flash", 0.0005637540000000001], '
    '["flash", 0.00020367400000000002], ["flash", 0.00013720600000000001], '
    '["outside", 0.0001], ["flash", 0.0001], ["flash", 6.091000000000001e-06], '
    '["flash", 5.671e-06], ["flash", 3.672e-06], ["outside", 2.3100000000000003e-06]]')


def test_device_only_sample_reduces_as_before(device_only):
    assert (device_only.busy_s, device_only.window_s) == (DEVICE_BUSY_S, DEVICE_WINDOW_S)
    assert json.dumps(device_only.top_programs(10)) == DEVICE_TOP
    assert json.dumps(device_only.idle_gaps(10)) == DEVICE_GAPS


@pytest.mark.parametrize("metric", READERS)
def test_device_only_sample_gives_no_program_metric(device_only, metric):
    assert read(metric, device_only, []) is None  # a trace without seifer.step
    assert read(metric, None, None) is None
    # no .xplane.pb of this run beside the readers: nothing read, nothing raised
    run = SimpleNamespace(cell="no-such-cell", seed=2 ** 40 + 7, device=device_only)
    assert reader(metric).read(run) is None


# -- the spans found again in the run's .xplane.pb -----------------------------

def _traced(results, cell, seed, stage):
    """A window with one ``seifer.step`` holding one ``seifer.stage``,
    traced on the CPU where the harness would write it; its DeviceTrace."""
    out = results / f"trace-{cell}-{seed}-{stage}"
    with jax.profiler.trace(str(out)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("seifer.step"):
                with jax.profiler.TraceAnnotation("seifer.stage", stage=stage, batch=4):
                    jax.numpy.ones(8).block_until_ready()
    return DeviceTrace.from_events(devtrace.events_from_xplane(devtrace.find_xplane(out)))


def test_program_spans_come_from_the_xplane_of_the_run_s_window(tmp_path):
    cell, seed = "c.x", 2 ** 40 + 7
    first = _traced(tmp_path, cell, seed, 0)
    second = _traced(tmp_path, cell, seed, 1)
    for trace, stage in ((first, 0), (second, 1)):
        run = SimpleNamespace(cell=cell, seed=seed, device=trace)
        found = spans.program_spans(run, tmp_path)
        # a collection hook that an earlier deployment installed may add seifer.gc
        step, stage_span = [s for s in found if s["name"] != spans.GC]
        assert (step["name"], stage_span["name"]) == (spans.STEP, spans.STAGE)
        assert stage_span["args"] == {"stage": stage, "batch": 4}
        assert step["thread"] == stage_span["thread"]
        assert [s for s in spans.window_spans(trace, found)
                if s["name"] != spans.GC] == [step, stage_span]
    other = SimpleNamespace(cell=cell, seed=seed + 1, device=first)
    assert spans.program_spans(other, tmp_path) is None
    assert spans.program_spans(SimpleNamespace(device=None), tmp_path) is None


# -- a traced tiny run on the CPU ----------------------------------------------

def test_a_traced_tiny_run_reports_the_program_metrics(tmp_path):
    man = Manifest.load(tiny_bench(tmp_path), tmp_path / "bench")
    cell = sorted(TINY_CELLS)[0]
    res = run_cell(man, cell, 2 ** 40 + 7, 1.0, True, require_chip=False,
                   out_dir=tmp_path / "results", log=lambda *a, **k: None)
    metrics = res["metrics"]
    for m in READERS:
        assert m in metrics and math.isfinite(metrics[m]["value"]), m
    assert metrics["stage_call_ms"]["value"] > 0
    # the CPU has no TPU plane: the chip reads idle throughout
    assert sum(metrics[m]["value"] for m in IDLE_READERS) <= metrics["idle_share.sat"]["value"] + 1e-9
