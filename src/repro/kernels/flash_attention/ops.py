"""Blockwise flash attention (jnp) with a custom VJP.

Why this exists (vs. differentiating an online-softmax scan): the backward
pass of a scanned online softmax saves its (m, l, acc) carry at EVERY step --
O(S * S/c) f32 -- which is what blows HBM on 32k prefill.  A custom VJP keeps
residuals at O(S) (output + logsumexp) and recomputes probabilities blockwise,
exactly like the FlashAttention kernel the Pallas version implements on TPU.

FLOP exactness: causal grids use *wraparound pairing* -- super-row r
processes q-rows (r, nq-1-r), touching exactly nq+1 kv-blocks -- so no
block above the diagonal is ever computed and the HLO flop count equals the
true masked-attention work.  Sliding-window grids visit a constant
ceil(window/c)+1 offsets per row.  All loop trip counts are static (the
roofline analyzer multiplies while bodies by trip count).

Layouts: "blocked" (default) slices (c, H, hd) windows directly from the
native (B, S, H, hd) tensors and transposes per block; "grouped" pre-
transposes the whole tensor to (B, KH, G, S, hd) -- simpler HLO but costs
three full HBM round-trips of q/k/v per call, which dominated the memory
roofline at 32k (EXPERIMENTS.md SPerf iteration 1 measures the difference).

Supports GQA (H = G * KH), logit softcap (gemma2), causal / bidirectional /
sliding-window masks.  Math: logits f32, probabilities bf16 into the MXU,
f32 accumulators.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.flash_attention.ref import attention_ref

DEFAULT_BLOCK = 1024
_NEG_INF = -1e30


def _blk(x: jax.Array, i, c: int, axis: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=axis)


def _mask(qpos, kpos, *, causal: bool, window: int):
    ok = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        ok &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        ok &= qpos[:, None] - kpos[None, :] < window
    ok &= (kpos >= 0)[None, :]  # clamped out-of-range blocks
    return ok


def _fwd_update(carry, qb, kb, vb, qpos, kpos, cfg):
    """Online-softmax update of one (q-block, kv-block) pair.

    qb (B,KH,G,c,hd) pre-scaled; kb/vb (B,KH,ck,hd).
    """
    m, l, acc = carry
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb, preferred_element_type=jnp.float32)
    if cfg["softcap"] > 0:
        s = cfg["softcap"] * jnp.tanh(s / cfg["softcap"])
    ok = _mask(qpos, kpos, causal=cfg["causal"], window=cfg["window"])
    s = jnp.where(ok[None, None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _sel(lane_sel, pair):
    """Select lane 0/1 of a stacked (2, ...) pytree by a traced bool."""
    return jax.tree.map(lambda t: jnp.where(lane_sel, t[0], t[1]), pair)


def _put(lane_sel, pair, new):
    return jax.tree.map(
        lambda t, n: jnp.stack(
            [jnp.where(lane_sel, n, t[0]), jnp.where(lane_sel, t[1], n)]
        ),
        pair, new,
    )


def _finalize(m, l, acc):
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return o, lse


def _row_plan(nq: int, nk: int, cfg) -> tuple[str, int]:
    if cfg["window"] > 0:
        wb = -(-cfg["window"] // cfg["block"])
        return "window", min(wb + 1, nk)
    if cfg["causal"]:
        return "wrap", nq + 1
    return "full", nk


# ---------------------------------------------------------------------------
# Block loaders (layout abstraction)
# ---------------------------------------------------------------------------

def _loaders(q, k, v, cfg):
    """Returns (load_q, load_kv, dims).  load_q pre-scales by hd^-0.5."""
    c = cfg["block"]
    if cfg["layout"] == "grouped":
        b, kh, g, sq, hd = q.shape
        scale = jnp.asarray(hd**-0.5, q.dtype)

        def load_q(i):
            return _blk(q, i, c, 3) * scale

        def load_kv(j):
            return _blk(k, j, c, 2), _blk(v, j, c, 2)

        return load_q, load_kv, (b, kh, g, sq, hd)
    # blocked: native (B, S, H, hd) / (B, S, KH, hd)
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = jnp.asarray(hd**-0.5, q.dtype)

    def load_q(i):
        qb = _blk(q, i, c, 1)  # (B, c, H, hd)
        qb = qb.reshape(b, c, kh, g, hd).transpose(0, 2, 3, 1, 4)
        return qb * scale

    def load_kv(j):
        kb = _blk(k, j, c, 1).transpose(0, 2, 1, 3)  # (B, KH, c, hd)
        vb = _blk(v, j, c, 1).transpose(0, 2, 1, 3)
        return kb, vb

    return load_q, load_kv, (b, kh, g, sq, hd)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _forward(q, k, v, cfg):
    """Returns o_rows (nq, B,KH,G,c,hd) f32 (row-permuted), lse likewise, and
    the static row permutation applied."""
    load_q, load_kv, (b, kh, g, sq, hd) = _loaders(q, k, v, cfg)
    c = cfg["block"]
    skv = k.shape[2] if cfg["layout"] == "grouped" else k.shape[1]
    nq, nk = sq // c, skv // c
    plan, steps = _row_plan(nq, nk, cfg)
    ar = jnp.arange(c)

    def lane_init(n_lane):
        return (
            jnp.full((n_lane, b, kh, g, c), _NEG_INF, jnp.float32),
            jnp.zeros((n_lane, b, kh, g, c), jnp.float32),
            jnp.zeros((n_lane, b, kh, g, c, hd), jnp.float32),
        )

    if plan == "wrap":
        half = nq // 2

        def super_row(_, r):
            lo, hi = r, nq - 1 - r
            q_lo, q_hi = load_q(lo), load_q(hi)

            def inner(carry, j):
                use_lo = j <= r
                qi = jnp.where(use_lo, lo, hi)
                kj = jnp.where(use_lo, j, j - (r + 1))
                qb = jnp.where(use_lo, q_lo, q_hi)
                kb, vb = load_kv(kj)
                lane = _sel(use_lo, carry)
                new = _fwd_update(lane, qb, kb, vb, qi * c + ar, kj * c + ar, cfg)
                return _put(use_lo, carry, new), None

            carry, _ = jax.lax.scan(inner, lane_init(2), jnp.arange(steps))
            return None, _finalize(*carry)

        _, (o_pairs, lse_pairs) = jax.lax.scan(super_row, None, jnp.arange(half))
        order = np.array([[r, nq - 1 - r] for r in range(half)]).reshape(-1)
        perm = np.argsort(order)
        o_rows = o_pairs.reshape((nq, b, kh, g, c, hd))[perm]
        lse_rows = lse_pairs.reshape((nq, b, kh, g, c))[perm]
    else:
        def row(_, i):
            qb = load_q(i)

            def inner(carry, t):
                kj = i - (steps - 1) + t if plan == "window" else t
                kjc = jnp.clip(kj, 0, nk - 1)
                kb, vb = load_kv(kjc)
                kpos = jnp.where(kj >= 0, kjc * c, -c) + ar
                new = _fwd_update(carry, qb, kb, vb, i * c + ar, kpos, cfg)
                return new, None

            m0 = (jnp.full((b, kh, g, c), _NEG_INF, jnp.float32),
                  jnp.zeros((b, kh, g, c), jnp.float32),
                  jnp.zeros((b, kh, g, c, hd), jnp.float32))
            carry, _ = jax.lax.scan(inner, m0, jnp.arange(steps))
            return None, _finalize(*carry)

        _, (o_rows, lse_rows) = jax.lax.scan(row, None, jnp.arange(nq))

    return o_rows, lse_rows, (b, kh, g, sq, hd)


def _rows_to_native(o_rows, dims, dtype):
    """(nq, B, KH, G, c, hd) -> (B, S, H, hd)."""
    nq, b, kh, g, c, hd = o_rows.shape
    o = o_rows.transpose(1, 0, 4, 2, 3, 5)  # (B, nq, c, KH, G, hd)
    return o.reshape(b, nq * c, kh * g, hd).astype(dtype)


def _rows_to_grouped(o_rows, dims, dtype):
    nq, b, kh, g, c, hd = o_rows.shape
    o = jnp.moveaxis(o_rows, 0, 3)  # (B, KH, G, nq, c, hd)
    return o.reshape(b, kh, g, nq * c, hd).astype(dtype)


# ---------------------------------------------------------------------------
# Backward (fused single pass over kv columns; dq scattered in-place)
# ---------------------------------------------------------------------------

def _bwd_block(qb, kb, vb, dob, lseb, db, qpos, kpos, cfg):
    """One (q-block, kv-block) tile: returns (dq_b, dk_b, dv_b) grouped."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb, preferred_element_type=jnp.float32)
    s = s * cfg["scale"]
    if cfg["softcap"] > 0:
        capped = cfg["softcap"] * jnp.tanh(s / cfg["softcap"])
        dcap = 1.0 - (capped / cfg["softcap"]) ** 2
    else:
        capped, dcap = s, None
    ok = _mask(qpos, kpos, causal=cfg["causal"], window=cfg["window"])
    capped = jnp.where(ok[None, None, None], capped, _NEG_INF)
    p = jnp.exp(capped - lseb[..., None])
    dp = jnp.einsum("bhgqd,bhkd->bhgqk", dob, vb, preferred_element_type=jnp.float32)
    ds = p * (dp - db[..., None])
    if dcap is not None:
        ds = ds * dcap
    pb = p.astype(vb.dtype)
    dsb = ds.astype(qb.dtype)
    dv_b = jnp.einsum("bhgqk,bhgqd->bhkd", pb, dob, preferred_element_type=jnp.float32)
    dk_b = jnp.einsum("bhgqk,bhgqd->bhkd", dsb, qb, preferred_element_type=jnp.float32) * cfg["scale"]
    dq_b = jnp.einsum("bhgqk,bhkd->bhgqd", dsb, kb, preferred_element_type=jnp.float32) * cfg["scale"]
    return dq_b, dk_b, dv_b


def _backward(q, k, v, o_native, lse_g, do_native, cfg):
    """All tensors in the configured layout; lse_g (B,KH,G,S) f32.

    Returns gradients in the SAME layout as the inputs.
    """
    c = cfg["block"]
    blocked = cfg["layout"] == "blocked"
    if blocked:
        b, sq, h, hd = q.shape
        kh = k.shape[2]
        g = h // kh
        skv = k.shape[1]
    else:
        b, kh, g, sq, hd = q.shape
        skv = k.shape[2]
    nq, nk = sq // c, skv // c
    ar = jnp.arange(c)

    d_full = (o_native.astype(jnp.float32) * do_native.astype(jnp.float32)).sum(-1)
    if blocked:
        d_g = d_full.reshape(b, sq, kh, g).transpose(0, 2, 3, 1)  # (B,KH,G,S)
    else:
        d_g = d_full

    dob = do_native.astype(q.dtype)

    def load_q(i):
        if blocked:
            qb = _blk(q, i, c, 1).reshape(b, c, kh, g, hd).transpose(0, 2, 3, 1, 4)
            do_b = _blk(dob, i, c, 1).reshape(b, c, kh, g, hd).transpose(0, 2, 3, 1, 4)
        else:
            qb = _blk(q, i, c, 3)
            do_b = _blk(dob, i, c, 3)
        return qb, do_b, _blk(lse_g, i, c, 3), _blk(d_g, i, c, 3)

    def load_kv(j):
        if blocked:
            return (_blk(k, j, c, 1).transpose(0, 2, 1, 3),
                    _blk(v, j, c, 1).transpose(0, 2, 1, 3))
        return _blk(k, j, c, 2), _blk(v, j, c, 2)

    def add_dq(dq_full, i, dq_b):
        # dq_full kept NATIVE (B, S, H, hd) f32 so no global transpose at the end
        dq_n = dq_b.transpose(0, 3, 1, 2, 4).reshape(b, c, kh * g, hd)
        old = jax.lax.dynamic_slice_in_dim(dq_full, i * c, c, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(dq_full, old + dq_n, i * c, axis=1)

    dq0 = jnp.zeros((b, sq, kh * g, hd), jnp.float32)
    bcfg = cfg

    if cfg["causal"] and cfg["window"] == 0:  # wraparound over columns
        half = nq // 2
        steps = nq + 1

        def super_col(dq_full, r):
            lo, hi = r, nq - 1 - r
            k_lo, v_lo = load_kv(lo)
            k_hi, v_hi = load_kv(hi)

            def inner(carry, t):
                dq_full, dkv = carry
                n_lo = nq - r
                use_lo = t < n_lo
                col = jnp.where(use_lo, lo, hi)
                row = jnp.where(use_lo, lo + t, hi + (t - n_lo))
                kb = jnp.where(use_lo, k_lo, k_hi)
                vb = jnp.where(use_lo, v_lo, v_hi)
                qb, dob_b, lseb, db = load_q(row)
                dq_b, dk_b, dv_b = _bwd_block(
                    qb, kb, vb, dob_b, lseb, db, row * c + ar, col * c + ar, bcfg
                )
                dq_full = add_dq(dq_full, row, dq_b)
                lane = _sel(use_lo, dkv)
                new = (lane[0] + dk_b, lane[1] + dv_b)
                return (dq_full, _put(use_lo, dkv, new)), None

            z = jnp.zeros((2, b, kh, c, hd), jnp.float32)
            (dq_full, dkv), _ = jax.lax.scan(inner, (dq_full, (z, z)), jnp.arange(steps))
            return dq_full, dkv

        dq_full, dkv_pairs = jax.lax.scan(super_col, dq0, jnp.arange(half))
        order = np.array([[r, nq - 1 - r] for r in range(half)]).reshape(-1)
        perm = np.argsort(order)
        dk_cols = dkv_pairs[0].reshape((nq, b, kh, c, hd))[perm]
        dv_cols = dkv_pairs[1].reshape((nq, b, kh, c, hd))[perm]
    else:
        if cfg["window"] > 0:
            wb = -(-cfg["window"] // c)
            steps = min(wb + 1, nq)
        else:
            steps = nq

        def col(dq_full, j):
            kb, vb = load_kv(j)

            def inner(carry, t):
                dq_full, dk_acc, dv_acc = carry
                row = j + t if cfg["window"] > 0 else t
                rowc = jnp.clip(row, 0, nq - 1)
                qb, dob_b, lseb, db = load_q(rowc)
                qpos = jnp.where(row < nq, rowc * c, -c) + ar
                dq_b, dk_b, dv_b = _bwd_block(
                    qb, kb, vb, dob_b, lseb, db, qpos, j * c + ar, bcfg
                )
                dq_full = add_dq(dq_full, rowc, dq_b)
                return (dq_full, dk_acc + dk_b, dv_acc + dv_b), None

            z = jnp.zeros((b, kh, c, hd), jnp.float32)
            (dq_full, dk_j, dv_j), _ = jax.lax.scan(inner, (dq_full, z, z), jnp.arange(steps))
            return dq_full, (dk_j, dv_j)

        dq_full, (dk_cols, dv_cols) = jax.lax.scan(col, dq0, jnp.arange(nk))

    if blocked:
        dk = dk_cols.transpose(1, 0, 3, 2, 4).reshape(b, nk * c, kh, hd)
        dv = dv_cols.transpose(1, 0, 3, 2, 4).reshape(b, nk * c, kh, hd)
        return dq_full, dk, dv
    dk = jnp.moveaxis(dk_cols, 0, 2).reshape(b, kh, nk * c, hd)
    dv = jnp.moveaxis(dv_cols, 0, 2).reshape(b, kh, nk * c, hd)
    dqg = dq_full.reshape(b, sq, kh, g, hd).transpose(0, 2, 3, 1, 4)
    return dqg, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public API
# ---------------------------------------------------------------------------

@lru_cache(maxsize=64)
def _make_flash(causal: bool, window: int, softcap: float, block: int,
                layout: str = "blocked"):
    cfg = dict(causal=causal, window=window, softcap=softcap, block=block,
               layout=layout)

    def _run_fwd(q, k, v):
        o_rows, lse_rows, dims = _forward(q, k, v, cfg)
        if layout == "blocked":
            o = _rows_to_native(o_rows, dims, q.dtype)
        else:
            o = _rows_to_grouped(o_rows, dims, q.dtype)
        b, kh, g, sq, hd = dims
        lse = jnp.moveaxis(lse_rows, 0, 3).reshape(b, kh, g, sq)
        return o, lse

    @jax.custom_vjp
    def fn(q, k, v):
        return _run_fwd(q, k, v)[0]

    def fwd(q, k, v):
        o, lse = _run_fwd(q, k, v)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        # o/do arrive in the configured layout (fwd saved them as returned),
        # and _backward both consumes and emits that layout -- its D
        # computation and grad reshapes branch on cfg["layout"] internally,
        # so no per-layout staging is needed here.  Grouped-layout gradient
        # parity vs attention_ref is pinned in tests/test_kernels.py.
        q, k, v, o, lse = res
        hd = q.shape[-1]
        bcfg = dict(cfg, scale=hd**-0.5)
        dq, dk, dv = _backward(q, k, v, o, lse, do, bcfg)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    fn.defvjp(fwd, bwd)
    return fn


def _block_for(sq: int, skv: int, block: int, causal: bool) -> int | None:
    c = min(block, sq, skv)
    while c >= 128:
        if sq % c == 0 and skv % c == 0 and (not causal or (sq // c) % 2 == 0):
            return c
        c //= 2
    return None


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, KH, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block: int = DEFAULT_BLOCK,
    layout: str = "blocked",
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise attention; falls back to the naive ref at tiny shapes.

    ``use_pallas=True`` runs the forward pass on the Pallas TPU kernel
    (``interpret=True`` runs it on CPU for CI); it is forward-only, which is
    what the serving executors need, and raises ``ValueError`` on shapes the
    kernel cannot tile rather than quietly computing elsewhere.  Without it
    the jnp blockwise path below runs, which has a custom VJP."""
    b, sq, h, hd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    if use_pallas:
        bq, bk = min(block, sq), min(block, skv)
        # self-attention only: the TPU kernel's grid pairs q/kv blocks by
        # index, so cross-length (sq != skv) shapes cannot run on it
        if sq != skv or sq % bq or skv % bk:
            raise ValueError(
                f"the Pallas flash kernel needs self-attention with the "
                f"sequence a multiple of the block; got sq={sq}, skv={skv}, "
                f"block={block}")
        return flash_attention_tpu(
            q, k, v, causal=causal, window=window, softcap=softcap,
            block_q=bq, block_k=bk, interpret=interpret,
        )
    c = _block_for(sq, skv, block, causal and window == 0)
    if c is None or sq < 2 * 128:
        return attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    g = h // kh
    fn = _make_flash(causal, window, float(softcap), c, layout)
    if layout == "grouped":
        qg = jnp.moveaxis(q.reshape(b, sq, kh, g, hd), 1, 3)
        kg = jnp.moveaxis(k, 1, 2)
        vg = jnp.moveaxis(v, 1, 2)
        o = fn(qg, kg, vg)
        return jnp.moveaxis(o, 3, 1).reshape(b, sq, h, hd)
    return fn(q, k, v)
