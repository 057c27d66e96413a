"""Kernel correctness: shape/dtype sweeps against the pure-jnp oracles.

Covers the jnp blockwise flash attention (fwd + custom VJP), the Pallas TPU
kernel in interpret mode, and the int8 quantize/dequantize pair."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quantize.kernel import dequantize_int8_tpu, quantize_int8_tpu
from repro.kernels.quantize.ref import dequantize_ref, quantize_ref


def _qkv(b, sq, skv, h, kh, hd, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, sq, h, hd), dtype),
        jax.random.normal(k2, (b, skv, kh, hd), dtype),
        jax.random.normal(k3, (b, skv, kh, hd), dtype),
    )


SWEEP = [
    # (b, sq, skv, h, kh, hd, causal, window, softcap, block, dtype)
    (2, 512, 512, 4, 2, 64, True, 0, 0.0, 128, jnp.float32),
    (1, 1024, 1024, 4, 4, 32, True, 0, 50.0, 256, jnp.float32),
    (2, 512, 512, 4, 1, 64, True, 200, 0.0, 128, jnp.float32),
    (2, 512, 512, 2, 2, 64, False, 0, 0.0, 128, jnp.float32),
    (1, 256, 768, 2, 2, 64, False, 0, 0.0, 128, jnp.float32),
    (1, 512, 512, 8, 2, 128, True, 0, 0.0, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("layout", ["blocked", "grouped"])
@pytest.mark.parametrize("case", SWEEP)
def test_flash_forward_matches_ref(case, layout):
    b, sq, skv, h, kh, hd, causal, window, softcap, block, dtype = case
    q, k, v = _qkv(b, sq, skv, h, kh, hd, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block=block, layout=layout)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("layout", ["blocked", "grouped"])
@pytest.mark.parametrize("case", SWEEP[:5])
def test_flash_grads_match_ref(case, layout):
    """Gradient parity vs attention_ref for BOTH layouts.  The grouped leg
    pins the custom-VJP backward on grouped-layout residuals -- the path the
    dead identical-branch staging in ``bwd`` used to (not) special-case."""
    b, sq, skv, h, kh, hd, causal, window, softcap, block, dtype = case
    q, k, v = _qkv(b, sq, skv, h, kh, hd, jnp.float32)
    kw = dict(causal=causal, window=window, softcap=softcap)
    gf = jax.grad(lambda *a: (flash_attention(*a, block=block, layout=layout,
                                              **kw) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (attention_ref(*a, **kw) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        scale = max(1e-6, float(jnp.max(jnp.abs(b_))))
        assert float(jnp.max(jnp.abs(a - b_))) / scale < 1e-4


@pytest.mark.parametrize("case", SWEEP)
def test_flash_use_pallas_dispatch_matches_ref(case):
    """The ops-level ``use_pallas`` knob (interpret mode) stays within the
    documented forward tolerance vs attention_ref.  Cross-length shapes
    (sq != skv) do not fit the kernel's grid: they raise instead of quietly
    taking the jnp path, so a served result always came from the kernel."""
    b, sq, skv, h, kh, hd, causal, window, softcap, block, dtype = case
    q, k, v = _qkv(b, sq, skv, h, kh, hd, dtype)
    kw = dict(causal=causal, window=window, softcap=softcap, block=block,
              use_pallas=True, interpret=True)
    if sq != skv:
        with pytest.raises(ValueError, match="self-attention"):
            flash_attention(q, k, v, **kw)
        return
    out = flash_attention(q, k, v, **kw)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", SWEEP)
def test_pallas_kernel_interpret_matches_ref(case):
    b, sq, skv, h, kh, hd, causal, window, softcap, block, dtype = case
    if sq != skv:
        pytest.skip("TPU kernel grid assumes aligned q/kv blocks")
    q, k, v = _qkv(b, sq, skv, h, kh, hd, dtype)
    out = flash_attention_tpu(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=block, block_k=block,
                              interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

@given(
    rows=st.integers(1, 8),
    dblocks=st.integers(1, 6),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=25, deadline=None)
def test_quantize_roundtrip_bounded(rows, dblocks, seed):
    block = 128
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, dblocks * block), jnp.float32)
    q, s = quantize_ref(x, block)
    y = dequantize_ref(q, s, dtype=jnp.float32)
    # symmetric int8: error <= scale/2 per element (small f32 rounding slack:
    # the exact bound can overshoot by ~3e-6 relative on unlucky draws)
    bound = np.repeat(np.asarray(s), block, axis=-1) * 0.5 * (1 + 1e-4) + 1e-9
    assert np.all(np.abs(np.asarray(y - x)) <= bound)


QUANT_SWEEP = [
    # (rows, d, block, dtype) -- incl. ragged last blocks (d % block != 0)
    (4, 512, 128, jnp.float32),
    (4, 512, 128, jnp.bfloat16),
    (3, 300, 128, jnp.float32),  # ragged: last block 44 wide
    (3, 300, 128, jnp.bfloat16),
    (2, 37, 256, jnp.float32),  # ragged: d < block entirely
    (1, 129, 128, jnp.bfloat16),  # ragged: one element past the boundary
]


@pytest.mark.parametrize("case", QUANT_SWEEP)
def test_quantize_error_bound_matches_reported(case):
    """Round-trip error <= INT8_MAX_REL_ERROR * per-block max -- the SAME
    constant the data plane's int8 codec reports to the planner's
    accuracy_tolerance check, across dtypes and ragged last-block shapes."""
    from repro.kernels.quantize import INT8_MAX_REL_ERROR

    rows, d, block, dtype = case
    x = jax.random.normal(jax.random.PRNGKey(rows * d), (rows, d), dtype)
    q, s = quantize_ref(x, block)
    assert q.shape == x.shape and s.shape == (rows, -(-d // block))
    y = dequantize_ref(q, s, dtype=jnp.float32, block=block)
    xf = np.asarray(x, np.float32)
    # per-element bound: rel error wrt the element's own block max (small
    # f32 rounding slack, as in the scale/2 bound above)
    per_block_max = np.repeat(np.asarray(s) * 127.0, block, axis=-1)[:, :d]
    bound = INT8_MAX_REL_ERROR * per_block_max * (1 + 1e-4) + 1e-9
    assert np.all(np.abs(np.asarray(y) - xf) <= bound)
    # the data plane reports exactly this constant as the codec error bound
    from repro.dataplane import get_codec

    assert get_codec("int8").error_bound == INT8_MAX_REL_ERROR


@pytest.mark.parametrize("case", QUANT_SWEEP)
def test_quantize_pallas_interpret_matches_ref_sweep(case):
    """The Pallas kernel (interpret mode) agrees with the jnp oracle on the
    same dtype/ragged sweep: identical codes, identical scales."""
    rows, d, block, dtype = case
    x = jax.random.normal(jax.random.PRNGKey(7 + rows + d), (rows, d), dtype)
    q1, s1 = quantize_ref(x, block)
    q2, s2 = quantize_int8_tpu(x, block=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    y1 = dequantize_ref(q1, s1, dtype=jnp.float32, block=block)
    y2 = dequantize_int8_tpu(q2, s2, dtype=jnp.float32, block=block,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-6, atol=1e-8)


def test_dequantize_ragged_requires_block():
    """When the trailing dim does not divide the scale count, no block can
    be inferred -- refuse instead of silently misassigning scales.  (An
    evenly-dividing ragged shape is indistinguishable from a smaller-block
    legacy layout, which is why every codec caller passes block= always.)"""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 301), jnp.float32)
    q, s = quantize_ref(x, 128)
    assert s.shape[-1] == 3  # ragged: 301 over 128-wide blocks
    with pytest.raises(ValueError, match="ragged"):
        dequantize_ref(q, s)
    assert dequantize_ref(q, s, block=128).shape == x.shape


def test_quantize_pallas_matches_ref():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 512), jnp.bfloat16)
    q1, s1 = quantize_ref(x, 128)
    q2, s2 = quantize_int8_tpu(x, block=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    y1 = dequantize_ref(q1, s1)
    y2 = dequantize_int8_tpu(q2, s2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y1, np.float32), np.asarray(y2, np.float32), rtol=1e-2, atol=1e-2
    )


# ---------------------------------------------------------------------------
# fused dequant-matmul
# ---------------------------------------------------------------------------

DQMM_SWEEP = [
    # (rows, d, dout, block, wdtype) -- incl. ragged trailing dims
    (16, 512, 64, 128, jnp.float32),
    (8, 300, 32, 128, jnp.float32),  # ragged: q cols + w rows get padded
    (4, 96, 48, 256, jnp.float32),  # ragged: d < block entirely
    (16, 512, 64, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DQMM_SWEEP)
def test_dequant_matmul_fused_matches_unfused(case):
    """The fused op computes EXACTLY dequantize-then-matmul (both f32): the
    fusion saves a materialized activation + dispatch, never accuracy."""
    from repro.kernels.quantize import dequant_matmul, dequantize_int8

    rows, d, dout, block, wdtype = case
    k1, k2 = jax.random.split(jax.random.PRNGKey(d + dout))
    x = jax.random.normal(k1, (rows, d), jnp.float32)
    w = jax.random.normal(k2, (d, dout), wdtype)
    q, s = quantize_ref(x, block)
    unfused = dequantize_int8(q, s, dtype=jnp.float32, block=block) @ w.astype(
        jnp.float32)
    fused = dequant_matmul(q, s, w, dtype=jnp.float32, block=block)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", DQMM_SWEEP)
def test_dequant_matmul_pallas_interpret_matches_ref(case):
    """Pallas dequant-matmul (interpret) vs the jnp oracle on the same
    shapes, including ragged trailing dims (zero-padded q cols keep the
    padded w rows inert)."""
    from repro.kernels.quantize import dequant_matmul

    rows, d, dout, block, wdtype = case
    k1, k2 = jax.random.split(jax.random.PRNGKey(3 * d + dout))
    x = jax.random.normal(k1, (rows, d), jnp.float32)
    w = jax.random.normal(k2, (d, dout), wdtype)
    q, s = quantize_ref(x, block)
    ref = dequant_matmul(q, s, w, dtype=jnp.float32, block=block)
    pal = dequant_matmul(q, s, w, dtype=jnp.float32, block=block,
                         use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dequant_matmul_leading_dims_and_default_dtype():
    """Leading batch dims flatten through the matmul; dtype defaults to w's."""
    from repro.kernels.quantize import dequant_matmul

    x = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 32), jnp.bfloat16)
    q, s = quantize_ref(x, 128)
    out = dequant_matmul(q, s, w, block=128)
    assert out.shape == (3, 4, 32) and out.dtype == jnp.bfloat16
    pal = dequant_matmul(q, s, w, block=128, use_pallas=True, interpret=True)
    assert pal.shape == out.shape and pal.dtype == out.dtype
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(out, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_quantize_scale_equivariance():
    """quantize(a*x) has scales a*scale(x) and identical codes (property)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256), jnp.float32)
    q1, s1 = quantize_ref(x, 128)
    q2, s2 = quantize_ref(4.0 * x, 128)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s2), 4.0 * np.asarray(s1), rtol=1e-6)


# ---------------------------------------------------------------------------
# ssm_scan (chunked SSD)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [(2, 256, 4, 64, 32, 64), (1, 512, 8, 64, 64, 128),
                                  (2, 256, 4, 64, 32, 64, 2), (1, 256, 8, 64, 32, 128, 8),
                                  (1, 256, 80, 64, 128, 128), (1, 256, 64, 64, 128, 128, 8),
                                  (2, 256, 6, 64, 32, 128, 2)])
def test_ssd_pallas_matches_ref(dims):
    """The kernel against the reference; a seventh entry gives B/C groups.
    The last three are mamba2-2.7b's heads (80 in one group), Nemotron's (64
    in 8 groups) and 3 heads a group, which blocks of 8 heads cannot split."""
    from repro.kernels.ssm_scan.ops import ssd_chunked
    from repro.kernels.ssm_scan.ref import ssd_ref

    b, s, h, hd, n, q = dims[:6]
    bc = (b, s, n) if len(dims) == 6 else (b, s, dims[6], n)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xs = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32) * 0.5
    bm = jax.random.normal(ks[1], bc) * 0.5
    cm = jax.random.normal(ks[2], bc) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.3)
    y_ref, _ = ssd_ref(xs, bm, cm, dt, a, chunk=q)
    y_pal = ssd_chunked(xs, bm, cm, dt, a, chunk=q, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_grouped_ssd_is_each_head_on_its_group(groups):
    """Grouped SSD, kernel and reference, equals each head scanned alone
    with its group's B and C (head h reads group h // (H/G))."""
    from repro.kernels.ssm_scan.ops import ssd_chunked
    from repro.kernels.ssm_scan.ref import ssd_ref

    b, s, h, hd, n, q = 2, 64, 8, 64, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    xs = jax.random.normal(ks[0], (b, s, h, hd)) * 0.5
    bm = jax.random.normal(ks[1], (b, s, groups, n)) * 0.5
    cm = jax.random.normal(ks[2], (b, s, groups, n)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.3)
    g = [i // (h // groups) for i in range(h)]
    want = jnp.concatenate([
        ssd_ref(xs[:, :, i:i + 1], bm[:, :, g[i]], cm[:, :, g[i]], dt[:, :, i:i + 1],
                a[i:i + 1], chunk=q)[0] for i in range(h)], axis=2)
    got_ref = ssd_ref(xs, bm, cm, dt, a, chunk=q)[0]
    got_pal = ssd_chunked(xs, bm, cm, dt, a, chunk=q, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_pal), np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("groups", [1, 8])
def test_ssd_kernel_operands_fold_groups(groups):
    """The kernel's pallas_call takes 7 operands: x as (B*H, S, dh), B and C
    as (B*G, S, N) -- for one group exactly (B, S, N), as before groups --
    then four per-position vectors as (B*H/hb, hb, S) rows; no operand ends
    in a dimension of 1.  y comes out (B*H/hb, hb*dh, S), positions on the
    lanes."""
    from repro.kernels.ssm_scan.kernel import ssd_blocking, ssd_chunked_tpu

    b, s, h, hd, n = 2, 256, 64, 64, 128
    bc = (b, s, n) if groups == 1 else (b, s, groups, n)
    specs = [jax.ShapeDtypeStruct(x, jnp.float32)
             for x in ((b, s, h, hd), bc, bc, (b, s, h), (h,))]
    jaxpr = jax.make_jaxpr(lambda *a: ssd_chunked_tpu(*a, chunk=128, interpret=True))(*specs)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    shapes = [v.aval.shape for v in calls[0].invars]
    hb = ssd_blocking(b, s, h, groups, hd, n, 128)[0]
    assert hb == (32 if groups == 1 else 8)
    assert shapes == [(b * h, s, hd), (b * groups, s, n), (b * groups, s, n)] + [
        (b * h // hb, hb, s)] * 4
    assert all(shape[-1] != 1 for shape in shapes)
    assert [v.aval.shape for v in calls[0].outvars] == [(b * h // hb, hb * hd, s)]


@pytest.mark.parametrize("shape,hb", [
    ((8, 2048, 80, 1, 64, 128, 128), 20),  # mamba2-2.7b: 80 heads, one group
    ((8, 2048, 64, 8, 64, 128, 128), 8),  # nemotron: 8 heads a group
    ((2, 256, 6, 2, 64, 32, 128), 3),  # 3 heads a group
    ((4, 8, 2, 1, 12, 4, 8), 2),  # demo_ssm: both heads
    ((1, 256, 4, 4, 64, 32, 128), 1),  # one head a group
])
def test_ssd_blocking_follows_the_shapes(shape, hb):
    """Heads per step: the largest divisor of the heads per group that fits
    the VMEM budget; the grid covers every head block and chunk once."""
    from repro.kernels.ssm_scan.kernel import VMEM_BUDGET, _step_vmem_bytes, ssd_blocking

    b, s, h, g, dh, n, q = shape
    got, grid = ssd_blocking(*shape)
    assert (got, grid) == (hb, (b * h // hb, s // q))
    assert (h // g) % got == 0
    assert got == 1 or _step_vmem_bytes(got, q, dh, n) <= VMEM_BUDGET


def test_ssd_chunk_invariance():
    from repro.kernels.ssm_scan.ref import ssd_ref

    b, s, h, hd, n = 1, 256, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    xs = jax.random.normal(ks[0], (b, s, h, hd)) * 0.5
    bm = jax.random.normal(ks[1], (b, s, n)) * 0.5
    cm = jax.random.normal(ks[2], (b, s, n)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.3)
    y1, _ = ssd_ref(xs, bm, cm, dt, a, chunk=32)
    y2, _ = ssd_ref(xs, bm, cm, dt, a, chunk=256)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# model-zoo executors on the kernel path
# ---------------------------------------------------------------------------

def _run_executor(factory, x, **knob):
    graph, executor_for_version = factory(**knob)
    return executor_for_version(0)(0, len(graph.layers), x)


@pytest.mark.parametrize("factory_name,shape", [
    ("demo_transformer", (256, 32)),
    ("demo_ssm", (8, 24)),
])
def test_zoo_executor_pallas_interpret_matches_ref(factory_name, shape):
    """demo_transformer/demo_ssm executors produce the same activations with
    the execution knob on (Pallas interpret) as on the jnp reference path --
    the whole point of the knob: same math, kernel-backed."""
    from repro.core import model_zoo

    factory = getattr(model_zoo, factory_name)
    x = jax.random.normal(jax.random.PRNGKey(9), shape, jnp.float32) * 0.5
    y_ref = _run_executor(factory, x)
    y_pal = _run_executor(factory, x, use_pallas=True, interpret=True)
    assert y_pal.shape == x.shape
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


def test_demo_transformer_fused_int8_stage_matches_decode():
    """A stage handed an int8 EncodedActivation via the fused dequant-matmul
    handler computes the same thing as decode-then-run, from any cut."""
    from repro.core.model_zoo import demo_transformer
    from repro.dataplane import get_codec
    from repro.dataplane.base import EncodedActivation

    graph, executor_for_version = demo_transformer()
    ex = executor_for_version(0)
    n = len(graph.layers)
    assert "int8" in ex.fused_codecs
    codec = get_codec("int8")
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(11), (256, 32))) * 0.5
    x = ex(0, 2, x)  # realistic mid-pipeline activation
    enc = EncodedActivation(codec, codec.encode(np.asarray(x)))
    for start in (2, n - 1):
        fused = ex(start, n, enc)
        decoded = ex(start, n, enc.decode())
        np.testing.assert_allclose(np.asarray(fused), np.asarray(decoded),
                                   atol=1e-5, rtol=1e-5)


def test_demo_mlp_has_no_fused_codecs():
    """Executors without per-layer fused handlers advertise none, so the
    serving engines keep transcoding on the wire for them."""
    from repro.core.model_zoo import demo_mlp

    _, executor_for_version = demo_mlp()
    assert executor_for_version(0).fused_codecs == frozenset()
