"""Transformer building blocks: norms, rotary, MLPs, GQA attention.

Attention comes in three execution strategies:
  * ``attention_full``     -- materializes (.., Sq, Skv) logits; used for
    short sequences and smoke tests.
  * ``attention_chunked``  -- flash-style pair-block streaming (exact FLOPs
    for causal/windowed masks: only valid (q-chunk, kv-chunk) pairs are
    computed); used for long prefill/train.  The Pallas kernel in
    ``kernels/flash_attention`` is the TPU-optimized version of this.
  * ``attention_decode``   -- one-token query against a KV cache.

All softmax math is fp32; params/activations are bf16.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import dense_init, matmul

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, *, gemma: bool = False, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if gemma else w.astype(jnp.float32)
    return (y * scale).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x: jax.Array, p: dict) -> jax.Array:
    if cfg.norm_kind == "layernorm":
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"], gemma=cfg.gemma_norm)


def init_norm(cfg, d: int) -> dict:
    if cfg.norm_kind == "layernorm":
        return {"w": jnp.ones((d,), jnp.bfloat16), "b": jnp.zeros((d,), jnp.bfloat16)}
    return {"w": jnp.zeros((d,), jnp.bfloat16) if cfg.gemma_norm else jnp.ones((d,), jnp.bfloat16)}


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    sin = jnp.sin(angles)[..., None, :]  # broadcast over heads
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg, key: jax.Array) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, f)),
            "w_up": dense_init(ks[1], (d, f)),
            "w_down": dense_init(ks[2], (f, d)),
        }
    return {"w_up": dense_init(ks[0], (d, f)), "w_down": dense_init(ks[1], (f, d))}


def mlp(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(matmul(x, p["w_gate"])) * matmul(x, p["w_up"])
    elif cfg.mlp_kind == "geglu":
        h = jax.nn.gelu(matmul(x, p["w_gate"]), approximate=True) * matmul(x, p["w_up"])
    elif cfg.mlp_kind == "relu2":  # squared ReLU, no gate (Nemotron-H)
        h = jnp.square(jax.nn.relu(matmul(x, p["w_up"]).astype(jnp.float32))).astype(x.dtype)
    else:
        h = jax.nn.gelu(matmul(x, p["w_up"]), approximate=True)
    from repro.models.common import matmul_reduced

    return matmul_reduced(h, p["w_down"])  # d_ff is TP-contracted


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    causal: bool = True
    window: int = 0  # 0 = unlimited
    softcap: float = 0.0
    chunk_q: int = 1024
    chunk_kv: int = 1024


def init_attention(cfg, key: jax.Array) -> dict:
    d = cfg.d_model
    hq = cfg.padded_heads  # padded heads: zero wo slice -> exact at init
    ks = jax.random.split(key, 4)
    wo = dense_init(ks[3], (hq, cfg.head_dim, d), scale=(cfg.n_heads * cfg.head_dim) ** -0.5)
    if hq > cfg.n_heads:
        wo = wo.at[cfg.n_heads :].set(0)
    p = {
        "wq": dense_init(ks[0], (d, hq, cfg.head_dim)),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, cfg.head_dim)),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, cfg.head_dim)),
        "wo": wo,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, cfg.head_dim), jnp.bfloat16)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    return p


def qkv_proj(cfg, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"]).astype(x.dtype)
    k = jnp.einsum("...d,dhk->...hk", x, p["wk"]).astype(x.dtype)
    v = jnp.einsum("...d,dhk->...hk", x, p["wv"]).astype(x.dtype)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    # bf16 dot output: the heads dim is TP-contracted, so the partial-sum
    # all-reduce this feeds moves bf16, not f32 (see common.matmul_reduced)
    return jax.lax.dot_general(
        o, p["wo"], (((o.ndim - 2, o.ndim - 1), (0, 1)), ((), ())),
    ).astype(o.dtype)


def self_attention(p: dict, x: jax.Array, attend) -> jax.Array:
    """Self-attention with no positional encoding (Nemotron-H's attention
    layers): q/k/v projections -> ``attend(q, k, v)`` -> output projection,
    under the ``seifer.attn`` name scope."""
    with jax.named_scope("seifer.attn"):
        q, k, v = qkv_proj(None, p, x)
        return out_proj(p, attend(q, k, v))


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(logits / cap) if cap > 0 else logits


def _mask_bias(qpos: jax.Array, kpos: jax.Array, spec: AttnSpec) -> jax.Array:
    """(Sq, Skv) additive bias in f32: 0 allowed / -inf masked."""
    ok = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if spec.causal:
        ok &= qpos[:, None] >= kpos[None, :]
    if spec.window > 0:
        ok &= qpos[:, None] - kpos[None, :] < spec.window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _gqa_split(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, S, H, hd) -> (B, S, KH, G, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def attention_full(
    q: jax.Array, k: jax.Array, v: jax.Array, spec: AttnSpec,
    q_offset: jax.Array | int = 0,
) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Skv, KH, hd).  Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    qg = _gqa_split(q, kh)
    scale = hd**-0.5
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    logits = _softcap(logits, spec.softcap)
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(k.shape[1])
    logits = logits + _mask_bias(qpos, kpos, spec)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", w, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def _pair_blocks(nq: int, nkv: int, spec: AttnSpec, chunk: int) -> tuple[np.ndarray, np.ndarray]:
    """Valid (q-chunk, kv-chunk) pairs for the mask — exact FLOPs, no dead blocks."""
    pairs = []
    for i in range(nq):
        q_lo, q_hi = i * chunk, (i + 1) * chunk - 1
        for j in range(nkv):
            k_lo = j * chunk
            if spec.causal and k_lo > q_hi:
                continue  # entirely above the diagonal
            if spec.window > 0 and (q_lo - ((j + 1) * chunk - 1)) >= spec.window:
                continue  # entirely outside the sliding window
            pairs.append((i, j))
    idx = np.asarray(pairs, dtype=np.int32)
    return idx[:, 0], idx[:, 1]


def attention_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, spec: AttnSpec,
) -> jax.Array:
    """Flash-style streaming attention (exact): scan over valid pair-blocks.

    Online-softmax carry (m, l, acc) is kept per q-chunk; pair-blocks are
    visited grouped by q-chunk so each chunk's carry is finalized in order.
    FLOPs match the true masked attention (no wasted blocks), which keeps
    the roofline accounting honest.
    """
    b, sq, h, hd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    c = min(spec.chunk_q, sq, skv)
    if sq % c or skv % c:
        raise ValueError(f"seq lens ({sq},{skv}) must divide chunk {c}")
    nq, nkv = sq // c, skv // c
    qi, kj = _pair_blocks(nq, nkv, spec, c)
    g = h // kh
    scale = hd**-0.5
    qg = _gqa_split(q, kh)  # (B, S, KH, G, hd)

    m0 = jnp.full((b, kh, g, nq, c), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kh, g, nq, c), jnp.float32)
    a0 = jnp.zeros((b, kh, g, nq, c, hd), jnp.float32)

    def step(carry, ij):
        m, l, acc, = carry
        i, j = ij
        qb = jax.lax.dynamic_slice_in_dim(qg, i * c, c, axis=1)  # (B,c,KH,G,hd)
        kb = jax.lax.dynamic_slice_in_dim(k, j * c, c, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, j * c, c, axis=1)
        logits = jnp.einsum(
            "bqhgk,bshk->bhgqs", qb.astype(jnp.float32) * scale, kb.astype(jnp.float32)
        )
        logits = _softcap(logits, spec.softcap)
        qpos = i * c + jnp.arange(c)
        kpos = j * c + jnp.arange(c)
        logits = logits + _mask_bias(qpos, kpos, spec)
        mi = jax.lax.dynamic_slice_in_dim(m, i, 1, axis=3)[:, :, :, 0]
        li = jax.lax.dynamic_slice_in_dim(l, i, 1, axis=3)[:, :, :, 0]
        ai = jax.lax.dynamic_slice_in_dim(acc, i, 1, axis=3)[:, :, :, 0]
        m_new = jnp.maximum(mi, logits.max(axis=-1))
        # guard fully-masked rows (m_new == -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.where(jnp.isfinite(mi), jnp.exp(mi - safe_m), 0.0)
        l_new = li * corr + p.sum(axis=-1)
        a_new = ai * corr[..., None] + jnp.einsum("bhgqs,bshk->bhgqk", p, vb.astype(jnp.float32))
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new[:, :, :, None], i, axis=3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new[:, :, :, None], i, axis=3)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new[:, :, :, None], i, axis=3)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (jnp.asarray(qi), jnp.asarray(kj)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KH,G,nq,c,hd)
    o = jnp.moveaxis(o.reshape(b, kh, g, sq, hd), 3, 1)  # (B,S,KH,G,hd)
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def attention_decode(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, cache_len: jax.Array,
    spec: AttnSpec,
) -> jax.Array:
    """q: (B, 1, H, hd); caches: (B, Smax, KH, hd); cache_len: () int32.

    The new token's K/V are assumed already written at cache_len - 1.
    """
    b, _, h, hd = q.shape
    kh = k_cache.shape[2]
    qg = _gqa_split(q, kh)
    scale = hd**-0.5
    # mixed-precision dot: bf16 cache never materializes in f32 (full-cache
    # converts were the decode memory whale -- SPerf llama decode iter. 2)
    logits = jnp.einsum(
        "bqhgk,bshk->bhgqs", (qg.astype(jnp.float32) * scale).astype(qg.dtype),
        k_cache, preferred_element_type=jnp.float32,
    )
    logits = _softcap(logits, spec.softcap)
    kpos = jnp.arange(k_cache.shape[1])
    qpos = cache_len - 1
    ok = kpos < cache_len
    if spec.window > 0:
        ok &= (qpos - kpos) < spec.window
    logits = jnp.where(ok[None, None, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", w.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, hd).astype(q.dtype)


def attend(q, k, v, spec: AttnSpec, *, chunk_threshold: int = 2048) -> jax.Array:
    """Dispatch: full attention for short seqs, blockwise flash for long.

    The flash path (kernels/flash_attention) has a custom VJP with O(S)
    residuals -- required for 4k-32k training memory -- and exact causal
    FLOPs via wraparound pairing.
    """
    if q.shape[1] >= chunk_threshold:
        import os

        if os.environ.get("REPRO_ATTN_STUB"):
            # shape-correct, traffic-free stand-in: lowering a cell with and
            # without it isolates the attention loop's HBM bytes (used to
            # derive the Pallas-kernelized memory term in EXPERIMENTS SPerf)
            b, s, h, hd = q.shape
            kh = k.shape[2]
            vm = jnp.mean(v, axis=1, keepdims=True)  # (B,1,KH,hd)
            qg = q.reshape(b, s, kh, h // kh, hd)
            return (qg * vm[:, :, :, None]).reshape(b, s, h, hd)
        from repro.kernels.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=spec.causal, window=spec.window,
            softcap=spec.softcap, block=spec.chunk_q,
        )
    return attention_full(q, k, v, spec)
