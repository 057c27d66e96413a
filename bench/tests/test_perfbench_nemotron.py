"""The hybrid expert configuration at a tiny size on the CPU (kernels in
interpret mode): its builder served through ``deploy()`` agrees with its
float32 reference and the float8 control does not; its roofline readers
count what the algorithms need; the routing counter counts the picks."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.harness import run_cell
from bench.manifest import Manifest, load_module
from perfbench_tiny import BENCH, REPO
from repro.launch.hlo_cost import analyze_hlo

BASE = "nemotron-3-nano-30b-a3b.ep8"
CELL = "tiny-nemotron.closed"
# d 128; 4 Mamba heads of 64 in 2 groups; 4 query and 2 KV heads; experts
# 4-7 of 8 held, top-2; the pattern's three kinds; 32-token prompts
TINY = {
    "hidden_size": 128, "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "mamba_num_heads": 4, "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "moe_intermediate_size": 64, "moe_shared_expert_intermediate_size": 96,
    "n_routed_experts": 4, "n_router_experts": 8, "first_held_expert": 4,
    "num_experts_per_tok": 2, "vocab_size": 256, "ref_block": 2, "answer_positions": 8,
}
LIMIT = 0.045  # CPU readings, seeds 11-13 and 2**40 + 11-13: program at most 0.0207, control at least 0.079
SEED = 2 ** 40 + 11

SSD = load_module(BENCH / "metrics/ssd_scan_roofline.py", "test_metric_")
GMM = load_module(BENCH / "metrics/moe_gmm_roofline.py", "test_metric_")


def tiny_config() -> dict:
    cfg = json.loads((BENCH / f"configs/{BASE}.json").read_text())
    cfg.update(TINY, name="tiny-nemotron", check_limit=LIMIT)
    return cfg


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """A copy of ``bench/`` with the tiny configuration, a small closed mix
    and a manifest that names one cell, added by files alone."""
    root = tmp_path_factory.mktemp("tiny-nemotron")
    dst = root / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (dst / "configs/tiny-nemotron.json").write_text(json.dumps(tiny_config()))
    for ext in ("build.py", "ref.py"):
        shutil.copy(BENCH / f"configs/{BASE}.{ext}", dst / f"configs/tiny-nemotron.{ext}")
    mix = json.loads((BENCH / "traffic/sat.json").read_text())
    mix.update(prompt_len=32, prompt_pool=16, clients=4, check={"sample": 3})
    mix["deployment"].update(max_batch=2, microbatch=2)
    (dst / "traffic/tiny-closed32.json").write_text(json.dumps(mix))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    man["configs"] = [{"name": "tiny-nemotron", "source": "tiny test size",
                       "file": "bench/configs/tiny-nemotron.json", "reduced": [], "why": "CPU test"}]
    man["workloads"] = [{"name": CELL, "config": "tiny-nemotron", "traffic": "tiny-closed32",
                         "chips": 1, "why": "CPU test"}]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return Manifest.load(root / "BENCHMARK.json", dst)


def run(manifest, control=False):
    return run_cell(manifest, CELL, SEED, 1.0, False, require_chip=False, control=control,
                    log=lambda *a, **k: None)


@pytest.fixture(scope="module")
def sound(manifest):
    return run(manifest)


def test_deployed_model_matches_the_reference(manifest, sound):
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["rel_err_max"]["value"] < LIMIT / 2
    assert sound["failed"] == 0 and sound["attempted"] > 0


def test_control_is_not_correct(manifest, sound):
    res = run(manifest, control=True)
    assert res["correct"] is False, res["checks"]
    control = res["checks"]["rel_err_max"]
    assert control["value"] > control["limit"] > 2 * sound["checks"]["rel_err_max"]["value"]


@pytest.mark.parametrize("fault", ["mixers_fp8", "mamba_one_group", "moe_held_dropped",
                                   "attn_one_kv_head"])
def test_each_fault_changes_the_answers(manifest, fault):
    """``bench/faults.py``: each fault moves every answer past float32
    rounding (at d 128 the Mamba and attention faults stay under the tiny
    limit: whether the cell's limit sees each fault is read on the chip) and
    leaves the reference without a fault as it was."""
    faults = load_module(BENCH / "faults.py", "test_bench_")
    cell = manifest.cell(CELL)
    cfg, ref = manifest.config_json(cell), manifest.reference(cell)
    weights = ref.init_weights(cfg, [5, 6])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg["vocab_size"])
    want = ref.forward(cfg, weights, tokens)
    errs = faults.fault_errors(ref, cfg, weights, tokens, 2, names=(fault,))
    assert list(errs) == [fault] and len(errs[fault]) == 4
    assert min(errs[fault]) > 1e-3
    assert jnp.array_equal(ref.forward(cfg, weights, tokens), want)


def test_layers_carry_their_own_kinds_bytes_and_flops(manifest):
    cell = manifest.cell(CELL)
    cfg, ref, build = manifest.config_json(cell), manifest.reference(cell), manifest.builder(cell)
    weights = ref.init_weights(cfg, [3, 4])
    graph, _ = build.build(cfg, weights, ref, seq=32, use_pallas=False, interpret=False)
    by_kind = {}
    for layer in graph.layers[1:-1]:
        by_kind.setdefault(layer.name[0], set()).add((layer.param_bytes, layer.flops))
    assert set(by_kind) == {"M", "E", "*"}
    assert all(len(v) == 1 for v in by_kind.values())  # one size per kind
    assert len({v.pop() for v in by_kind.values()}) == 3  # three sizes in one chain
    total = sum(layer.flops for layer in graph.layers)
    assert total == pytest.approx(ref.flops_per_request(cfg, 32), rel=1e-6)


def test_routing_counter_counts_the_picks(manifest):
    routes = load_module(BENCH / "routes.py", "test_bench_")
    cell = manifest.cell(CELL)
    cfg, ref, build = manifest.config_json(cell), manifest.reference(cell), manifest.builder(cell)
    weights = ref.init_weights(cfg, [5, 6])
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, cfg["vocab_size"])
    out = routes.count_routes(cfg, weights, ref, build, tokens, use_pallas=False)
    picks = 4 * 32 * cfg["num_experts_per_tok"] * cfg["hybrid_override_pattern"].count("E")
    assert out["picks"] == picks and len(out["rows"]) == cfg["n_routed_experts"]
    assert out["held_share"] == pytest.approx(sum(out["rows"]) / picks)
    assert 0.2 < out["held_share"] < 0.8  # half the experts held
    assert 0.0 <= out["mismatch"] < 0.05


def _hlo(f, *specs):
    return analyze_hlo(jax.jit(f).lower(*specs).compile().as_text())


def test_ssd_reader_counts_grouped_operands():
    """Grouped B/C reach the kernel as (B*G, S, N): the SSD reader's own
    ``_cost`` then counts the grouped algorithm's operations."""
    from repro.kernels.ssm_scan.ref import ssd_ref

    b, s, h, g, dh, n, q = 2, 256, 8, 4, 64, 32, 64
    f32 = jnp.float32
    specs = [jax.ShapeDtypeStruct(x, f32) for x in
             ((b, s, h, dh), (b, s, g, n), (b, s, g, n), (b, s, h), (h,))]
    cost = _hlo(lambda xs, bm, cm, dt, a: ssd_ref(xs, bm, cm, dt, a, chunk=q)[0], *specs)
    ins = [("f32", (b * h, s, dh)), ("f32", (b * g, s, n)), ("f32", (b * g, s, n))]
    run = type("Run", (), {"cfg": {"assumed": {"kernel_chunk": q}}})()
    ops, nbytes = SSD._cost([], ins + [("f32", (1,))] * 4, run)
    assert ops == pytest.approx(cost.flops, rel=1e-6)
    assert nbytes <= cost.bytes


def test_gmm_ops_match_balanced_grouped_products():
    """2 r k n operations for r rows among the held experts, the least
    traffic under what XLA moves for the same products."""
    m, k, n, held, experts = 1024, 384, 256, 4, 16
    r = m * held // experts
    bf = jnp.bfloat16
    cost = _hlo(lambda x, w: jnp.einsum("gmk,gkn->gmn", x, w),
                jax.ShapeDtypeStruct((held, r // held, k), bf),
                jax.ShapeDtypeStruct((held, k, n), bf))
    ops, nbytes = GMM.gmm_ops_bytes(m, k, n, held, experts)
    assert ops == pytest.approx(cost.flops, rel=1e-6)
    assert nbytes <= cost.bytes


def test_gmm_reader_matches_the_kernel_call():
    outs = [("bf16", (98304, 1856))]
    ins = [("s32", ()), ("s32", (129,)), ("s32", (319,)), ("s32", (319,)), ("s32", (1,)),
           ("bf16", (98304, 2688)), ("bf16", (16, 2688, 1856))]
    assert GMM._match("gmm", outs, ins)
    assert not GMM._match("ssd_chunked", outs, ins)
    assert not GMM._match("gmm", [("bf16", (98304, 2688))], ins)
    run = type("Run", (), {"cfg": {"n_router_experts": 128}})()
    ops, _ = GMM._cost(outs, ins, run)
    assert ops == pytest.approx(2 * 98304 * 16 / 128 * 2688 * 1856)


@pytest.mark.parametrize("reader,ins", [
    ("ssd_scan_roofline", [("bf16", (512, 2048, 64)), ("f32", (64, 2048, 128)),
                           ("f32", (64, 2048, 128))] + [("f32", (512, 1, 2048))] * 4),
    ("flash_attn_roofline", [("bf16", (256, 2048, 128)), ("bf16", (16, 2048, 128)),
                             ("bf16", (16, 2048, 128))]),
    ("moe_gmm_roofline", [("s32", (129,)), ("bf16", (98304, 2688)), ("bf16", (16, 2688, 1856))]),
])
def test_cell_rooflines_cost_with_the_configuration(reader, ins):
    """Each roofline the cell reports prices its kernel's event with what
    the configuration file gives (a key its ``_cost`` reads is there)."""
    cfg = json.loads((BENCH / f"configs/{BASE}.json").read_text())
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = [m["name"] for m in man["per_layer"] if f"{BASE}.sat" in m.get("workloads", [])]
    assert reader in listed
    mod = load_module(BENCH / f"metrics/{reader}.py", "test_metric_")
    run = type("Run", (), {"cfg": cfg})()
    ops, nbytes = mod._cost([("bf16", ins[-2][1])], ins, run)
    assert ops > 0 and nbytes > 0
