"""Data-plane coverage: codec registry, transforms, byte/cost models, the
joint codec x placement assignment, and the engine's end-to-end pinning.

The anchor tests are the last two groups: every registered codec (plus
``"auto"``) deployed on a bandwidth-constrained cluster must measure within
5% of ``Plan.predicted_throughput`` (the engine and the planner read the
same ``dataplane.hops`` records), and a lossy codec must *really* alter
the activations crossing links -- the transform runs in the serving path,
not just in the byte model.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ClusterSpec, DeploymentSpec, deploy
from repro.cluster import NodeFailed
from repro.core.bottleneck import service_times
from repro.core.graph import chain, make_partitions
from repro.core.model_zoo import demo_mlp
from repro.core.placement import CommGraph
from repro.dataplane import (
    UnknownCodecError,
    assign_link_codecs,
    codec_table,
    default_codec,
    get_codec,
    link_charge_s,
    list_codecs,
    register_codec,
    select_codec,
)

WIDTH = 32


def _star_cluster(mesh_bw: float, hosting: int = 4, dispatcher_bw: float = 1e9):
    """Fast dispatcher links, ``mesh_bw`` across the hosting mesh -- the
    constrained resource is exactly the inter-stage activation path."""
    n = hosting + 1
    bw = np.full((n, n), float(mesh_bw))
    bw[0, :] = bw[:, 0] = dispatcher_bw
    np.fill_diagonal(bw, 0.0)
    graph, _ = demo_mlp(d=WIDTH)
    cap = np.full(n, graph.total_param_bytes / 3.0)
    cap[0] = -1.0
    return CommGraph(bw=bw, node_capacity=cap)


def _deploy(codec, mesh_bw=1e4, **kw):
    graph, executor_for_version = demo_mlp(d=WIDTH)
    return deploy(DeploymentSpec(
        model=graph,
        executor_for_version=executor_for_version,
        cluster=ClusterSpec(comm=_star_cluster(mesh_bw)),
        codec=codec,
        microbatch=1,
        **kw,
    ))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_contains_the_required_codecs():
    names = set(list_codecs())
    assert names >= {"identity", "fp16", "int8", "topk-sparse"}
    assert default_codec() == "identity"
    assert list_codecs()[0] == "identity"  # default listed first


def test_unknown_codec_raises_with_suggestions():
    with pytest.raises(UnknownCodecError) as ei:
        get_codec("int-8")
    assert "int8" in str(ei.value)  # did-you-mean
    assert "identity" in str(ei.value)  # registered names listed


def test_duplicate_codec_registration_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        register_codec("identity")(type("Dup", (), {}))


def test_codec_table_reports_every_codec():
    rows = codec_table()
    assert {r["name"] for r in rows} == set(list_codecs())
    by = {r["name"]: r for r in rows}
    assert by["identity"]["default"] == "yes"
    assert float(by["int8"]["wire_ratio_f32"]) < 0.5


# ---------------------------------------------------------------------------
# Transforms + byte model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("asarray", [np.asarray, jnp.asarray],
                         ids=["numpy", "jax"])
def test_roundtrip_error_within_reported_bound(asarray):
    """decode(encode(x)) stays within each codec's reported error bound,
    on both the jax path (what the engine feeds) and the numpy fallback."""
    x = asarray(np.random.default_rng(0).normal(
        size=(4, 37)).astype(np.float32))
    scale = float(np.max(np.abs(np.asarray(x))))
    for name in list_codecs():
        codec = get_codec(name)
        y = codec.transcode(x)
        assert y.shape == x.shape
        err = float(np.max(np.abs(np.asarray(y) - np.asarray(x)))) / scale
        assert err <= codec.error_bound * (1 + 1e-4) + 1e-9, name


def test_int8_on_the_pallas_path_refuses_host_arrays():
    """Configured for the Pallas kernel, the int8 codec quantizes jax arrays
    through it and refuses numpy input instead of quantizing on the host."""
    codec = get_codec("int8").configured(use_pallas=True, interpret=True)
    x = np.random.default_rng(0).normal(size=(4, 256)).astype(np.float32)
    with pytest.raises(TypeError, match="jax array"):
        codec.encode(x)
    y = codec.transcode(jnp.asarray(x))
    assert float(np.max(np.abs(np.asarray(y) - x))) <= (
        codec.error_bound * float(np.max(np.abs(x))) * (1 + 1e-4))


def test_identity_is_exact_and_free():
    codec = get_codec("identity")
    x = jnp.ones((3, 5))
    assert codec.transcode(x) is x
    assert codec.wire_bytes(1000.0) == 1000.0
    assert codec.encode_cost_s(1e9, 1e9) == 0.0
    assert codec.error_bound == 0.0


def test_topk_keeps_the_largest_magnitudes_exactly():
    codec = get_codec("topk-sparse")
    x = np.arange(1, 17, dtype=np.float32).reshape(4, 4)  # all distinct
    y = codec.transcode(x)
    k = codec._k(x.size)
    top = np.sort(np.abs(x).ravel())[-k:]
    kept = np.abs(y[y != 0])
    np.testing.assert_array_equal(np.sort(kept), top)  # survivors exact
    assert np.count_nonzero(y) == k


def test_compressed_bytes_layouts():
    """Exact on-wire sizes: identity = raw, fp16 = half, int8 = 1 B/elem +
    one f32 scale per (ragged) block, topk = kept * (value + int32 index)."""
    shape = (4, 300)  # ragged over int8's 256-wide blocks
    n = 4 * 300
    assert get_codec("identity").compressed_bytes(shape) == n * 4
    assert get_codec("fp16").compressed_bytes(shape) == n * 2
    assert get_codec("int8").compressed_bytes(shape) == n + 4 * (4 * 2)
    topk = get_codec("topk-sparse")
    assert topk.compressed_bytes(shape) == topk._k(n) * 8
    # the analytic wire ratio agrees with the exact layout on block-aligned
    # shapes (what the byte-counted simulator charges)
    aligned = (4, 512)
    for name in list_codecs():
        codec = get_codec(name)
        exact = codec.compressed_bytes(aligned)
        assert codec.wire_bytes(4 * 512 * 4) == pytest.approx(exact, rel=0.01)


def test_fp16_clamps_out_of_range_instead_of_overflowing():
    """Values past float16's finite range must degrade to the range edge,
    never become inf and poison downstream stages."""
    codec = get_codec("fp16")
    x = np.array([[1e6, -1e6, 3.5]], np.float32)
    for y in (codec.transcode(x), codec.transcode(jnp.asarray(x))):
        y = np.asarray(y, np.float32)
        assert np.all(np.isfinite(y))
        np.testing.assert_allclose(y, [[65504.0, -65504.0, 3.5]], rtol=1e-3)


def test_int8_numpy_fallback_matches_the_jax_ref_exactly():
    """The codec's numpy fallback and kernels/quantize/ref.py implement one
    algorithm twice (ref must stay jnp to lower under jit); this pin makes
    any drift -- scale rule, epsilon, clip range, ragged padding -- fail
    loudly instead of silently forking the wire format."""
    from repro.dataplane.codecs import _np_dequantize, _np_quantize
    from repro.kernels.quantize.ref import dequantize_ref, quantize_ref

    for shape in ((4, 512), (3, 300), (2, 37)):  # aligned + ragged
        x = np.random.default_rng(sum(shape)).normal(
            size=shape).astype(np.float32)
        block = 256
        qn, sn = _np_quantize(x, block)
        qj, sj = quantize_ref(jnp.asarray(x), block)
        np.testing.assert_array_equal(qn, np.asarray(qj))
        np.testing.assert_allclose(sn, np.asarray(sj), rtol=1e-7)
        yn = _np_dequantize(qn, sn, block)
        yj = dequantize_ref(qj, sj, dtype=jnp.float32, block=block)
        np.testing.assert_allclose(yn, np.asarray(yj), rtol=1e-6, atol=1e-8)


def test_int8_codec_reports_the_kernel_error_bound():
    """One number, two consumers: the quantize kernel's tested bound IS the
    figure the planner's accuracy_tolerance check uses."""
    from repro.kernels.quantize import INT8_MAX_REL_ERROR

    assert get_codec("int8").error_bound == INT8_MAX_REL_ERROR


# ---------------------------------------------------------------------------
# Selection + assignment
# ---------------------------------------------------------------------------

def test_select_codec_compresses_slow_links_and_leaves_fast_ones_raw():
    # slow link: wire time dominates -> densest admissible codec
    assert select_codec(1e6, 1e3, src_flops=1e9, dst_flops=1e9) == "int8"
    # fast link: codec compute dominates -> identity (zero-cost) wins
    assert select_codec(1e3, 1e12, src_flops=1e9, dst_flops=1e9) == "identity"


def test_select_codec_respects_the_tolerance():
    assert select_codec(1e6, 1e3, tolerance=0.0) == "identity"
    assert select_codec(1e6, 1e3, tolerance=1e-3) == "fp16"
    assert select_codec(1e6, 1e3, tolerance=0.004) == "int8"


def test_link_charge_is_encode_plus_transfer_plus_decode():
    codec = get_codec("int8")
    nbytes, bw, f = 1e6, 1e4, 1e9
    expect = (codec.encode_cost_s(nbytes, f)
              + codec.wire_bytes(nbytes) / bw
              + codec.decode_cost_s(nbytes, f))
    assert link_charge_s(codec, nbytes, bw, src_flops=f, dst_flops=f) == expect
    assert link_charge_s(codec, nbytes, 0.0) == float("inf")


def test_assignment_keeps_dispatcher_hops_raw():
    bw = np.full((4, 4), 1e3)
    codecs = assign_link_codecs([100, 200, 200, 100], [1, 2, 3], bw,
                                codec="int8", dispatcher=0)
    assert codecs == ("identity", "int8", "int8", "identity")
    auto = assign_link_codecs([100, 200, 200, 100], [1, 2, 3], bw,
                              codec="auto", dispatcher=0)
    assert auto[0] == auto[-1] == "identity"
    assert all(c == "int8" for c in auto[1:-1])


def test_assignment_skips_colocated_hops():
    bw = np.full((3, 3), 1e3)
    codecs = assign_link_codecs([0, 200, 0], [1, 1], bw,
                                codec="auto", dispatcher=0)
    assert codecs == ("identity", "identity", "identity")


def test_service_times_charges_the_codec_window():
    graph = chain("c", [(100, 1000)] * 2, in_bytes=0)
    parts = make_partitions(graph, [0])
    bw = np.full((3, 3), 1e3)
    codec = get_codec("int8")
    base_compute, base_links = service_times(parts, [1, 2], bw,
                                             flops_per_node=1e9)
    compute, links = service_times(
        parts, [1, 2], bw, flops_per_node=1e9,
        codecs=["identity", "int8", "identity"])
    assert compute == base_compute  # codec work rides the link window
    assert links[1] == pytest.approx(
        link_charge_s(codec, 1000.0, 1e3, src_flops=1e9, dst_flops=1e9))
    assert links[1] < base_links[1]  # compression shrank the wire time


# ---------------------------------------------------------------------------
# Spec validation
# ---------------------------------------------------------------------------

def _spec(**kw):
    graph, _ = demo_mlp(d=WIDTH)
    kw.setdefault("model", graph)
    kw.setdefault("cluster", ClusterSpec(comm=_star_cluster(1e4)))
    return DeploymentSpec(**kw)


def test_spec_rejects_unknown_codec_with_suggestions():
    issues = _spec(codec="int-8").validate()
    assert [i.code for i in issues] == ["unknown_codec"]
    assert "int8" in issues[0].message  # did-you-mean rides the issue


def test_spec_rejects_negative_tolerance():
    issues = _spec(codec="auto", accuracy_tolerance=-0.5).validate()
    assert [i.code for i in issues] == ["bad_tolerance"]


def test_spec_rejects_named_codec_over_tolerance():
    issues = _spec(codec="topk-sparse", accuracy_tolerance=0.01).validate()
    assert [i.code for i in issues] == ["codec_exceeds_tolerance"]
    # auto under the same tolerance is fine: it picks within the budget
    assert _spec(codec="auto", accuracy_tolerance=0.01).validate() == ()
    # and a lossless codec trivially fits a zero tolerance
    assert _spec(codec="identity", accuracy_tolerance=0.0).validate() == ()


# ---------------------------------------------------------------------------
# End to end: deploy -> serve -> measure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", [*list_codecs(), "auto"])
def test_engine_measures_the_plan_prediction_per_codec(codec):
    """Measured steady-state rate == predicted (shared service_times model,
    codec windows included) within 5%, for every codec on a link-bound
    cluster."""
    d = _deploy(codec)
    for _ in range(24):
        d.submit(jnp.ones((WIDTH,)) * 0.1)
    d.drain()
    assert len(d.loop.failed) == 0 and len(d.loop.completed) == 24
    measured = d.loop.steady_state_throughput()
    assert measured == pytest.approx(d.plan.predicted_throughput, rel=0.05)


def test_auto_beats_identity_on_a_link_bound_cluster():
    """The acceptance criterion: link time >> compute time under identity,
    so auto must pick a compressing codec and improve >= 1.5x."""
    rates = {}
    for codec in ("identity", "auto"):
        d = _deploy(codec)
        for _ in range(24):
            d.submit(jnp.ones((WIDTH,)) * 0.1)
        d.drain()
        rates[codec] = d.loop.steady_state_throughput()
        if codec == "auto":
            interior = d.plan.codecs[1:-1]
            assert any(c != "identity" for c in interior), d.plan.codecs
    assert rates["auto"] >= 1.5 * rates["identity"]


def test_tolerance_zero_forces_lossless_links():
    d = _deploy("auto", accuracy_tolerance=0.0)
    assert set(d.plan.codecs) == {"identity"}


def test_lossy_codec_really_transforms_the_activations():
    """int8 runs decode(encode(x)) on every link crossing: outputs differ
    from the identity deployment but stay within a few quantization steps
    through the whole tanh chain."""
    outs = {}
    for codec in ("identity", "int8"):
        d = _deploy(codec)
        d.submit(jnp.ones((WIDTH,)) * 0.1)
        (req,) = d.drain()
        outs[codec] = np.asarray(req.result, np.float32)
    assert not np.array_equal(outs["identity"], outs["int8"])
    assert np.max(np.abs(outs["identity"] - outs["int8"])) < 0.05


@pytest.mark.parametrize("codec", ["identity", "fp16", "int8", "topk-sparse"])
def test_every_reader_takes_the_one_hop_record(codec):
    """Each hop is costed once: the record's window is the codec's link
    charge and its wire bytes the codec's, and the engine's link report,
    ``service_times`` and ``InferencePipeline.wire_bytes`` all read it."""
    d = _deploy(codec)
    control = d.control
    pipe, disp = control.pipeline, control.dispatcher
    compute_s, links = control.pipeline_times()
    bw, flops = disp.probed.bw, disp.node_flops()
    assert len(links) == len(pipe.pods) + 1
    c = get_codec(codec)
    for h, link in enumerate(links[1:-1], start=1):
        assert link.codec.name == codec
        assert link.raw_bytes == pipe.boundary_bytes[h - 1]
        assert link.wire_bytes == c.wire_bytes(link.raw_bytes)
        assert link.seconds == link_charge_s(
            c, link.raw_bytes, float(bw[link.src, link.dst]),
            src_flops=flops[link.src], dst_flops=flops[link.dst])
        assert pipe.wire_bytes(h - 1) == link.wire_bytes
    assert [(r["codec"], r["raw_bytes"], r["wire_bytes"], r["link_s"])
            for r in d.loop.metrics()["links"]] == [
        (link.codec.name, link.raw_bytes, link.wire_bytes, link.seconds)
        for link in links]
    graph = control.desired.graph
    assert service_times(
        [p.partition for p in pipe.pods], pipe.path(), bw,
        flops_per_node=flops, in_bytes=graph.in_bytes,
        out_bytes=graph.layers[-1].out_bytes, dispatcher=disp.leader,
        codecs=d.plan.codecs,
    ) == (compute_s, [link.seconds for link in links])


def test_engine_reports_per_link_compression_and_utilization():
    d = _deploy("int8")
    for _ in range(8):
        d.submit(jnp.ones((WIDTH,)) * 0.1)
    d.drain()
    links = d.loop.metrics()["links"]
    assert len(links) == len(d.plan.path) + 1
    interior = [ln for ln in links if 0 < ln["hop"] < len(d.plan.path)]
    for ln in interior:
        assert ln["codec"] == "int8"
        assert ln["compression_x"] == pytest.approx(2048 / 520, rel=1e-6)
        assert ln["transfers"] == 8
        assert 0.0 < ln["utilization"] <= 1.0
    # dispatcher round-trip hops stay raw
    assert links[0]["codec"] == links[-1]["codec"] == "identity"


def test_replan_keeps_the_codec_config():
    """Swapping a strategy on a live deployment must not silently drop the
    data-plane config: the new planner inherits codec + tolerance."""
    d = _deploy("auto")
    d.replan(placer="greedy")
    assert d.control.planner.codec == "auto"
    assert any(c != "identity" for c in d.plan.codecs[1:-1])
    assert d.plan.codecs == tuple(d.control.pipeline.link_codecs)


def test_recovery_reassigns_codecs_for_the_new_path():
    """Joint codec x placement survives churn: a NodeFailed re-placement
    re-solves the per-link assignment and the plan/pipeline/engine agree."""
    graph, executor_for_version = demo_mlp(d=WIDTH)
    d = deploy(DeploymentSpec(
        model=graph,
        executor_for_version=executor_for_version,
        # a spare hosting node, so the 4-partition pipeline survives a kill
        cluster=ClusterSpec(comm=_star_cluster(1e4, hosting=5)),
        codec="auto",
        microbatch=1,
    ))
    for _ in range(16):
        d.submit(jnp.ones((WIDTH,)) * 0.1)
    d.step()
    victim = d.control.pipeline.pods[1].node_id
    d.inject(NodeFailed(victim))
    d.drain()
    assert len(d.loop.completed) == 16
    plan = d.plan
    assert len(plan.codecs) == len(plan.path) + 1
    assert plan.codecs == tuple(d.control.pipeline.link_codecs)
    assert any(c != "identity" for c in plan.codecs[1:-1])
    measured = d.loop.steady_state_throughput()
    assert measured > 0
