"""The reduction from a device trace to the per-layer numbers, on a small
recorded trace of one TPU v5 lite chip (``data/v5e_trace_sample.json``)."""

from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench.devtrace import DeviceTrace, hlo_shapes, op_name
from bench.manifest import load_module
from bench.peaks import PEAKS, peaks_for
from perfbench_tiny import BENCH

SAMPLE = BENCH / "tests/data/v5e_trace_sample.json"


@pytest.fixture(scope="module")
def trace():
    return DeviceTrace.load_json(SAMPLE)


def _run(trace, **cfg):
    return SimpleNamespace(device=trace, peaks=peaks_for("TPU v5 lite"),
                           cfg={"assumed": cfg})


def test_hlo_text_gives_names_and_shapes():
    text = ('%quantize_int8.1 = (s8[8192,2560]{1,0:T(8,128)(4,1)}, f32[8192,10]{1,0}) '
            'custom-call(bf16[8192,2560]{1,0:T(8,128)(2,1)} %bitcast.5), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[8192,2560]{1,0}}')
    outs, ins = hlo_shapes(text)
    assert outs == [("s8", (8192, 2560)), ("f32", (8192, 10))]
    assert ins == [("bf16", (8192, 2560))]
    assert op_name(text) == "quantize_int8"


def test_busy_time_is_the_union_of_op_intervals_inside_the_window(trace):
    a, b = trace.window_ns
    busy = trace.busy_intervals()
    assert all(a <= s < t <= b for s, t in busy)
    assert all(t1 <= s2 for (_, t1), (s2, _) in zip(busy, busy[1:]))  # disjoint
    total_ops = sum(e["dur_ns"] for e in trace.ops) * 1e-9
    assert 0 < trace.busy_s <= min(total_ops, trace.window_s)


def test_kernels_are_found_with_their_shapes(trace):
    ssd = trace.kernel_ops(lambda n, o, i: n.startswith("ssd_chunked"))
    assert len(ssd) == 1 and ssd[0]["ins"][0] == ("bf16", (320, 2048, 64))
    codec = trace.kernel_ops(lambda n, o, i: n in ("quantize_int8", "dequantize_int8"))
    assert sorted(e["name"] for e in codec) == ["dequantize_int8", "quantize_int8"]


@pytest.mark.parametrize("metric,assumed", [
    ("ssd_scan_roofline", {"kernel_chunk": 128}),
    ("int8_codec_roofline", {}),
    ("flash_attn_roofline", {}),
])
def test_roofline_shares_lie_in_zero_to_one_hundred(trace, metric, assumed):
    share = load_module(BENCH / f"metrics/{metric}.py", "test_metric_").read(_run(trace, **assumed))
    assert share is not None and 0 < share <= 100


def test_a_roofline_with_no_kernel_in_the_window_reports_nothing(trace):
    empty = DeviceTrace([], [], trace.host, 1)
    reader = load_module(BENCH / "metrics/ssd_scan_roofline.py", "test_metric_")
    assert reader.read(_run(empty, kernel_chunk=128)) is None
    assert reader.read(SimpleNamespace(device=None)) is None


def test_breakdown_names_programs_and_attributes_gaps(trace):
    top = trace.top_programs(10)
    assert top and all(isinstance(n, str) and s > 0 for n, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert not any("(" in n for n, _ in top)  # hashes stripped
    gaps = trace.idle_gaps(10)
    assert gaps and gaps[0][1] >= gaps[-1][1]
    # the longest gap opens while the host traced the flash call
    assert gaps[0][0] == "flash"
    busy, window = trace.busy_s, trace.window_s
    assert sum(s for _, s in trace.idle_gaps(10 ** 6)) == pytest.approx(window - busy, rel=1e-6)


def test_an_unknown_device_has_no_peaks():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_sample_file_is_small_and_plain_json():
    data = json.loads(SAMPLE.read_text())
    assert SAMPLE.stat().st_size < 64 * 1024
    assert {"ops", "modules", "host"} <= set(data)
