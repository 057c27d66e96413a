"""Mistral NeMo 12B, first 16 layers: seeded weights and the plain float32
reference.

The reference follows the published decoder (Mistral-Nemo-Base-2407's
params.json) in straightforward ``jax.numpy`` at float32 and
``Precision.HIGHEST``: embedding, then per layer RMSNorm -> q/k/v
projections -> rotary embedding (the split-halves convention) -> causal
grouped-query softmax attention -> output projection -> residual add ->
RMSNorm -> SwiGLU MLP -> residual add.  It returns the residual stream
after the last layer held here.  It imports nothing of the program under
test.

``forward`` takes ``dot``, the product of every projection: float32 at
HIGHEST for the reference, ``dot_fp8`` for the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def init_weights(cfg: dict, words: list[int]) -> dict:
    """Every weight in bf16, made on the device in one jitted call."""
    d, h, kh, hd, f = (cfg["dim"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["hidden_dim"])

    def make(key):
        def normal(k, shape):
            return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)

        k_embed, k_layers = jax.random.split(key)
        layers = []
        for i in range(cfg["n_layers"]):
            ks = jax.random.split(jax.random.fold_in(k_layers, i), 7)
            layers.append({
                "attn_norm": jnp.ones((d,), jnp.bfloat16),
                "wq": normal(ks[0], (d, h, hd)),
                "wk": normal(ks[1], (d, kh, hd)),
                "wv": normal(ks[2], (d, kh, hd)),
                "wo": normal(ks[3], (h, hd, d)),
                "mlp_norm": jnp.ones((d,), jnp.bfloat16),
                "w_gate": normal(ks[4], (d, f)),
                "w_up": normal(ks[5], (d, f)),
                "w_down": normal(ks[6], (f, d)),
            })
        return {"embed": normal(k_embed, (cfg["vocab_size"], d)), "layers": layers}

    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    return jax.jit(make)(key)


def flops_per_request(cfg: dict, seq: int) -> float:
    """Model FLOPs of one prompt: projections, MLP, and causal attention
    (QK^T and PV over the lower triangle, seq^2 / 2 pairs)."""
    d, h, kh, hd, f = (cfg["dim"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["hidden_dim"])
    proj = 2 * seq * d * (2 * h * hd + 2 * kh * hd)
    mlp = 2 * seq * 3 * d * f
    attn = 2 * 2 * h * hd * seq * seq / 2
    return float(cfg["n_layers"] * (proj + mlp + attn))


def dot_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def dot_fp8(spec: str, a, b):
    return jnp.einsum(spec, _fp8(a.astype(jnp.float32)), _fp8(b.astype(jnp.float32)),
                      precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta: float):
    """x (B, L, H, hd): rotate the pairs (i, i + hd/2) by position * freq_i."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(cfg: dict, lw: dict, x, dot):
    """One decoder layer in float32.  x (B, L, d)."""
    eps = cfg["norm_eps"]
    h, kh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    lw = jax.tree.map(lambda t: t.astype(jnp.float32), lw)
    a = rmsnorm(x, lw["attn_norm"], eps)
    q = rotary(dot("bld,dhk->blhk", a, lw["wq"]), cfg["rope_theta"])
    k = rotary(dot("bld,dhk->blhk", a, lw["wk"]), cfg["rope_theta"])
    v = dot("bld,dhk->blhk", a, lw["wv"])
    g = h // kh
    b, length = x.shape[:2]
    qg = q.reshape(b, length, kh, g, hd)
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    logits = jnp.where(causal, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", w, v, precision=HIGHEST).reshape(b, length, h, hd)
    x = x + dot("blhk,hkd->bld", o, lw["wo"])
    m = rmsnorm(x, lw["mlp_norm"], eps)
    gate = jax.nn.silu(dot("bld,df->blf", m, lw["w_gate"]))
    up = dot("bld,df->blf", m, lw["w_up"])
    return x + dot("blf,fd->bld", gate * up, lw["w_down"])


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key, dot):
    cfg = dict(cfg_key)
    return jax.jit(lambda lw, x: layer_forward(cfg, lw, x, dot))


def forward(cfg: dict, weights: dict, tokens, dot=dot_f32):
    """The residual stream after the last layer, float32, layer by layer.
    tokens (B, L) int32 -> (B, L, d)."""
    key = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))
    layer = _jitted(key, dot)
    x = weights["embed"][tokens].astype(jnp.float32)
    for lw in weights["layers"]:
        x = layer(lw, x)
    return x
