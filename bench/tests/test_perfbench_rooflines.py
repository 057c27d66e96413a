"""Each roofline metric's operation and byte function, checked against the
program's HLO cost analysis (``repro.launch.hlo_cost``) at one shape."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.manifest import load_module
from perfbench_tiny import BENCH
from repro.launch.hlo_cost import analyze_hlo

SSD = load_module(BENCH / "metrics/ssd_scan_roofline.py", "test_metric_")
FLASH = load_module(BENCH / "metrics/flash_attn_roofline.py", "test_metric_")
CODEC = load_module(BENCH / "metrics/int8_codec_roofline.py", "test_metric_")


def _hlo(f, *specs):
    return analyze_hlo(jax.jit(f).lower(*specs).compile().as_text())


def test_ssd_ops_match_the_chunked_algorithm():
    from repro.kernels.ssm_scan.ref import ssd_ref

    b, s, h, dh, n, q = 2, 256, 4, 64, 32, 64
    f32 = jnp.float32
    specs = [jax.ShapeDtypeStruct(x, f32) for x in
             ((b, s, h, dh), (b, s, n), (b, s, n), (b, s, h), (h,))]
    cost = _hlo(lambda xs, bm, cm, dt, a: ssd_ref(xs, bm, cm, dt, a, chunk=q)[0], *specs)
    ops, nbytes = SSD.ssd_ops_bytes(b, s, h, dh, n, q, x_bytes=4)
    assert ops == pytest.approx(cost.flops, rel=1e-6)
    assert nbytes <= cost.bytes  # the least traffic, under what XLA moves


def test_flash_ops_match_full_attention():
    b, s, h, kh, hd = 2, 256, 4, 2, 64
    g = h // kh

    def attn(q, k, v):  # bidirectional: every (query, key) pair
        qg = q.reshape(b, s, kh, g, hd)
        p = jax.nn.softmax(jnp.einsum("bqhgd,bkhd->bhgqk", qg, k), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(b, s, h, hd)

    bf = jnp.bfloat16
    cost = _hlo(attn, jax.ShapeDtypeStruct((b, s, h, hd), bf),
                jax.ShapeDtypeStruct((b, s, kh, hd), bf), jax.ShapeDtypeStruct((b, s, kh, hd), bf))
    ops, nbytes = FLASH.flash_ops_bytes(b * h, b * kh, s, hd, causal=False)
    assert ops == pytest.approx(cost.flops, rel=1e-6)
    causal_ops, _ = FLASH.flash_ops_bytes(b * h, b * kh, s, hd, causal=True)
    assert causal_ops == pytest.approx(ops / 2)
    assert nbytes <= cost.bytes


def test_codec_bytes_are_the_least_traffic_of_the_quantize_round_trip():
    from repro.kernels.quantize.ref import dequantize_ref, quantize_ref

    rows, d, block = 512, 2560, 256
    x = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)
    enc = _hlo(lambda x: quantize_ref(x, block=block), x)
    _, enc_bytes = CODEC.quantize_ops_bytes(rows, d, block, in_bytes=2)
    assert enc_bytes == pytest.approx(rows * d * 3 + rows * (d // block) * 4)
    assert enc_bytes <= enc.bytes
    q = jax.ShapeDtypeStruct((rows, d), jnp.int8)
    s = jax.ShapeDtypeStruct((rows, d // block), jnp.float32)
    dec = _hlo(lambda q, s: dequantize_ref(q, s, dtype=jnp.bfloat16, block=block), q, s)
    _, dec_bytes = CODEC.dequantize_ops_bytes(rows, d, block, out_bytes=2)
    assert dec_bytes <= dec.bytes
