"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode cannot see what the TPU compiler refuses (block shapes that
do not tile, vector shape casts Mosaic does not support), so each kernel on
the served path is compiled here for a described -- not attached --
``v5e:2x2`` chip at the shapes the demo models and the GPipe boundary use,
and the compiled program must contain the kernel (``tpu_custom_call``).
Nothing runs; no chip is needed.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.moe_gmm.kernel import gmm_tpu
from repro.kernels.quantize.kernel import (
    dequant_matmul_tpu,
    dequantize_int8_tpu,
    quantize_int8_tpu,
)
from repro.kernels.ssm_scan.kernel import ssd_chunked_tpu


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compilation cache off
    (a compile for a described chip is written but cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs outside the checkout
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


F32, I8, BF16, I32 = jnp.float32, jnp.int8, jnp.bfloat16, jnp.int32
# demo_transformer: (batch, seq 256, d 32), 4 query / 2 kv heads of dim 8;
# demo_ssm: (batch, seq 8, d 24), 2 heads of dim 12, state 4
CASES = {
    "flash_window0": (
        lambda q, k, v: flash_attention_tpu(
            q, k, v, causal=True, window=0, softcap=50.0,
            block_q=128, block_k=128),
        [((4, 256, 4, 8), F32), ((4, 256, 2, 8), F32), ((4, 256, 2, 8), F32)]),
    "flash_window128": (
        lambda q, k, v: flash_attention_tpu(
            q, k, v, causal=True, window=128, softcap=50.0,
            block_q=128, block_k=128),
        [((4, 256, 4, 8), F32), ((4, 256, 2, 8), F32), ((4, 256, 2, 8), F32)]),
    "quantize_transformer": (
        lambda x: quantize_int8_tpu(x, 256), [((4, 256, 32), F32)]),
    "quantize_ssm": (
        lambda x: quantize_int8_tpu(x, 256), [((3, 8, 24), F32)]),
    "dequantize_ssm": (
        lambda q, s: dequantize_int8_tpu(q, s, dtype=F32, block=256),
        [((3, 8, 24), I8), ((3, 8, 1), F32)]),
    "dequantize_gpipe_block32": (
        lambda q, s: dequantize_int8_tpu(q, s, dtype=F32, block=32),
        [((16, 32), I8), ((16, 1), F32)]),
    "dequant_matmul_transformer": (
        lambda q, s, w: dequant_matmul_tpu(q, s, w, dtype=F32, block=256),
        [((4, 256, 32), I8), ((4, 256, 1), F32), ((32, 64), F32)]),
    "dequant_matmul_block32": (
        lambda q, s, w: dequant_matmul_tpu(q, s, w, dtype=F32, block=32),
        [((16, 32), I8), ((16, 1), F32), ((32, 32), F32)]),
    "ssd_demo_ssm": (
        lambda xs, bm, cm, dt, a: ssd_chunked_tpu(xs, bm, cm, dt, a, chunk=8),
        [((4, 8, 2, 12), F32), ((4, 8, 4), F32), ((4, 8, 4), F32),
         ((4, 8, 2), F32), ((2,), F32)]),
    "ssd_chunk128": (
        lambda xs, bm, cm, dt, a: ssd_chunked_tpu(xs, bm, cm, dt, a, chunk=128),
        [((1, 512, 8, 64), F32), ((1, 512, 64), F32), ((1, 512, 64), F32),
         ((1, 512, 8), F32), ((8,), F32)]),
    # nemotron-3-nano-30b-a3b.ep8 at its cell's shapes: a batch of 8 x 2048
    # tokens, 64 heads of 64 in 8 B/C groups of state 128; 16 of 128
    # experts held, 8 x 2048 x 6 sorted picks through the up and down
    # projections
    "ssd_nemotron_groups8": (
        lambda xs, bm, cm, dt, a: ssd_chunked_tpu(xs, bm, cm, dt, a, chunk=128),
        [((8, 2048, 64, 64), BF16), ((8, 2048, 8, 128), F32), ((8, 2048, 8, 128), F32),
         ((8, 2048, 64), F32), ((64,), F32)]),
    # mamba2-2.7b.sat-int8 at its cell's shapes: 80 heads of 64 in one B/C
    # group of state 128
    "ssd_mamba_cell": (
        lambda xs, bm, cm, dt, a: ssd_chunked_tpu(xs, bm, cm, dt, a, chunk=128),
        [((8, 2048, 80, 64), BF16), ((8, 2048, 128), F32), ((8, 2048, 128), F32),
         ((8, 2048, 80), F32), ((80,), F32)]),
    "gmm_nemotron_up": (
        lambda x, w, sizes: gmm_tpu(x, w, sizes, out_dtype=BF16),
        [((98304, 2688), BF16), ((16, 2688, 1856), BF16), ((128,), I32)]),
    "gmm_nemotron_down": (
        lambda x, w, sizes: gmm_tpu(x, w, sizes, out_dtype=BF16),
        [((98304, 1856), BF16), ((16, 1856, 2688), BF16), ((128,), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 1, name


# a compiled stage of each kernel-backed demo model: the kernels' custom calls
# survive inside the one program, as the roofline readers match them
STAGES = {
    "demo_ssm": ((4, 8, 24), "bench.metrics.ssd_scan_roofline"),
    "demo_transformer": ((4, 256, 32), "bench.metrics.flash_attn_roofline"),
}


def kernel_calls(text):
    """(name, output shapes, operand shapes) of each Pallas kernel call in a
    compiled module's text, operands read from its layout constraints (the
    profiler's op text, which the readers see, writes them inline)."""
    import re

    from bench.devtrace import hlo_shapes, is_kernel, op_name

    calls = []
    for line in text.splitlines():
        if not is_kernel(line):
            continue
        tail = line.split("operand_layout_constraints={", 1)[1]
        depth = 1
        for end, ch in enumerate(tail):
            depth += (ch == "{") - (ch == "}")
            if depth == 0:
                break
        ins = [(dt, tuple(int(d) for d in dims.split(",") if d)) for dt, dims
               in re.findall(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]", tail[:end])]
        calls.append((op_name(line), hlo_shapes(line)[0], ins))
    return calls


@pytest.mark.parametrize("model", sorted(STAGES))
def test_compiled_stage_keeps_its_kernels_for_v5e(model, one_chip):
    import importlib

    from repro.core import model_zoo

    shape, reader = STAGES[model]
    match = importlib.import_module(reader)._match
    graph, efv = getattr(model_zoo, model)(use_pallas=True)
    n = len(graph.layers)
    prog = efv(0).program(1, n, jnp.zeros(shape, F32))  # traced on the CPU
    args = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
            for c in prog.consts]
    x = jax.ShapeDtypeStruct(shape, F32, sharding=one_chip)
    calls = kernel_calls(prog.fn.lower(args, x).compile().as_text())
    found = [name for name, outs, ins in calls if match(name, outs, ins)]
    assert len(found) == n - 1, (model, calls)
    if model == "demo_ssm":
        assert set(found) == {"ssd_chunked"}
        assert all(len(ins) == 7 for _, _, ins in calls)
    else:
        assert all(len(ins) == 3 and all(len(s) == 3 for _, s in ins)
                   for _, _, ins in calls)


@pytest.mark.parametrize("name,reader", [
    ("ssd_nemotron_groups8", "bench.metrics.ssd_scan_roofline"),
    ("gmm_nemotron_up", "bench.metrics.moe_gmm_roofline"),
])
def test_nemotron_kernels_are_what_their_readers_match_for_v5e(name, reader, one_chip):
    """The grouped SSD and the grouped matmul compiled at the cell's shapes,
    through the entry points the blocks call, are the custom calls their
    roofline readers pick: the SSD's B and C as (B*G, S, N) among 7
    operands, the gmm's rows and held weights last."""
    import importlib
    from functools import partial

    from repro.kernels.moe_gmm import gmm
    from repro.kernels.ssm_scan import ssd_chunked

    entry = {"ssd_nemotron_groups8": partial(ssd_chunked, chunk=128, use_pallas=True),
             "gmm_nemotron_up": partial(gmm, out_dtype=BF16, use_pallas=True)}
    fn, shapes = entry[name], CASES[name][1]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    calls = kernel_calls(jax.jit(fn).lower(*args).compile().as_text())
    match = importlib.import_module(reader)._match
    found = [(n, outs, ins) for n, outs, ins in calls if match(n, outs, ins)]
    assert len(found) == 1, calls
    _, _, ins = found[0]
    if name.startswith("ssd"):
        assert len(ins) == 7 and ins[1][1] == (8 * 8, 2048, 128)
    else:
        assert ins[-2][1] == (98304, 2688) and ins[-1][1] == (16, 2688, 1856)


# what the SSD's roofline reader priced at the parent commit, and the
# temporaries the parent's compiled SSD held (v5e, no chip): (ins[0], ins[1],
# ssd_ops_bytes, temp bytes)
SSD_CELLS = {
    "ssd_mamba_cell": (("bf16", (640, 2048, 64)), ("f32", (8, 2048, 128)),
                       (64961380352.0, 530579456.0), 2013685248),
    "ssd_nemotron_groups8": (("bf16", (512, 2048, 64)), ("f32", (64, 2048, 128)),
                             (55834574848.0, 545259520.0), 1677915136),
}


@pytest.mark.parametrize("name", sorted(SSD_CELLS))
def test_ssd_at_cell_shapes_is_priced_as_before_for_v5e(name, one_chip):
    """The SSD entry compiled at each SSM cell's shapes holds one custom call
    that ``ssd_scan_roofline`` matches, priced from the same operand shapes
    (so the same operations and bytes) as at the parent, and under half the
    parent's temporaries (no padded (., S, 1) columns, y in its own layout)."""
    from functools import partial
    from types import SimpleNamespace

    from bench.metrics import ssd_scan_roofline as reader
    from repro.kernels.ssm_scan import ssd_chunked

    x0, x1, cost, parent_temp = SSD_CELLS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in CASES[name][1]]
    compiled = jax.jit(partial(ssd_chunked, chunk=128, use_pallas=True)).lower(*args).compile()
    found = [(n, outs, ins) for n, outs, ins in kernel_calls(compiled.as_text())
             if reader._match(n, outs, ins)]
    assert len(found) == 1, found
    _, outs, ins = found[0]
    assert len(ins) == 7 and ins[0] == x0 and ins[1] == ins[2] == x1
    run = SimpleNamespace(cfg={"assumed": {"kernel_chunk": 128}})
    assert reader._cost(outs, ins, run) == cost
    assert compiled.memory_analysis().temp_size_in_bytes < parent_temp / 2


@pytest.mark.parametrize("name,groups,d", [("ssd_mamba_cell", 1, 2560),
                                           ("ssd_nemotron_groups8", 8, 2688)])
def test_mixer_reads_the_ssd_output_in_place_for_v5e(name, groups, d, one_chip):
    """The mixer's tail after the SSD (+ D x, grouped gated norm,
    out-projection) compiled at each SSM cell's shapes reads the kernel's
    output as it lies: no copy takes the ``ssd_chunked`` call's result."""
    import re
    from types import SimpleNamespace

    from repro.kernels.ssm_scan import ssd_chunked
    from repro.models import ssm

    shapes = CASES[name][1]
    b, s, h, dh = shapes[0][0]
    cfg = SimpleNamespace(d_model=d, ssm_expand=2, ssm_state=128, ssm_conv_width=4,
                          ssm_groups=groups, ssm_heads=h)

    def tail(xs, bm, cm, dt, a, dskip, z, norm_w, out_proj):
        y = ssd_chunked(xs, bm, cm, dt, a, chunk=128, use_pallas=True)
        y = y + xs.astype(F32) * dskip[None, None, :, None]
        p = {"norm_w": norm_w, "out_proj": out_proj}
        return ssm._gate_out(cfg, p, y.reshape(b, s, h * dh), z)

    shapes = shapes + [((h,), F32), ((b, s, h * dh), BF16), ((h * dh,), F32),
                       ((h * dh, d), BF16)]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip) for sh, dt in shapes]
    text = jax.jit(tail).lower(*args).compile().as_text()
    kernel = re.findall(r"%(ssd_chunked[\w.]*) = ", text)
    assert len(kernel) == 1, kernel
    views = set(kernel)  # the kernel's result and its bitcasts
    for dst, src in re.findall(r"%([\w.\-]+) = \S+ bitcast\(%([\w.\-]+)\)", text):
        if src in views:
            views.add(dst)
    copied = re.findall(r"= \S+ copy\(%([\w.\-]+)\)", text)
    assert not views & set(copied), sorted(views & set(copied))
