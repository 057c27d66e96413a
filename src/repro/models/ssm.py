"""Mamba2 (SSD) blocks: chunked parallel scan for train/prefill, O(1) decode.

The chunked SSD algorithm (Mamba2 paper Sec. 6) splits the sequence into
chunks of ``chunk`` steps; within a chunk the recurrence is materialized as a
(Q, Q) masked "attention" (quadratic in the chunk only), and a (dh, N) state
is carried between chunks (``kernels/ssm_scan``: the Pallas kernel, or its
jnp reference with ``use_pallas=False``).  All gate math is fp32.

Layout: heads of size HEAD_DIM, scalar-per-head A (the Mamba2 restriction),
B and C in ``ssm_groups`` groups (Mamba2's ``ngroups``; head h reads group
h // (heads / groups)), and a gated RMSNorm over groups of d_inner / groups
channels.  A config gives d_inner as ``ssm_heads * HEAD_DIM`` when it sets
``ssm_heads`` (Nemotron-H publishes 64 heads of 64 beside ``expand`` 2, which
would give another width), else as ``ssm_expand * d_model``.  Configs that
name none of ``ssm_heads``, ``ssm_groups`` and ``ssm_norm_eps`` get one
group and eps 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.ops import ssd_chunked
from repro.models.common import dense_init

HEAD_DIM = 64
DEFAULT_CHUNK = 256


def ssm_dims(cfg) -> tuple[int, int, int]:
    """(d_inner, n_heads, state N) for the mamba tower of this config."""
    heads = getattr(cfg, "ssm_heads", 0)
    d_in = heads * HEAD_DIM if heads else cfg.ssm_expand * cfg.d_model
    return d_in, d_in // HEAD_DIM, max(cfg.ssm_state, 16)


def ssm_groups(cfg) -> int:
    """B/C groups (Mamba2's ``ngroups``)."""
    return getattr(cfg, "ssm_groups", 1)


def _conv_dim(cfg) -> int:
    d_in, _, n = ssm_dims(cfg)
    return d_in + 2 * ssm_groups(cfg) * n


def init_mamba(cfg, key: jax.Array) -> dict:
    d = cfg.d_model
    d_in, h, n = ssm_dims(cfg)
    conv_dim = _conv_dim(cfg)
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], (d, d_in + conv_dim + h)),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv_width, conv_dim), scale=0.3),
        "conv_b": jnp.zeros((conv_dim,), jnp.bfloat16),
        "A_log": jnp.zeros((h,), jnp.float32),  # A = -exp(A_log) = -1 at init
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm_w": jnp.ones((d_in,), jnp.bfloat16),
        "out_proj": dense_init(ks[3], (d_in, d), scale=d_in**-0.5),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv.  x: (B, S, C); w: (K, C).  Sum of shifts."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(
        jax.lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1)
        * w[i].astype(x.dtype)
        for i in range(k)
    )
    return out + b.astype(x.dtype)


def _split_proj(cfg, p: dict, x: jax.Array):
    """x (B,S,d) -> z (B,S,d_in), xBC (B,S,d_in+2GN), dt (B,S,H) fp32."""
    d_in = ssm_dims(cfg)[0]
    conv_dim = _conv_dim(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"]).astype(x.dtype)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in : d_in + conv_dim]
    dt = zxbcdt[..., d_in + conv_dim :].astype(jnp.float32)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    return z, xbc, dt


def _split_xbc(cfg, xbc: jax.Array):
    """Conv output (..., d_in+2GN) -> x (..., H, HEAD_DIM), and B, C
    (..., G, N) in fp32."""
    d_in, h, n = ssm_dims(cfg)
    g = ssm_groups(cfg)
    lead = xbc.shape[:-1]
    xs = xbc[..., :d_in].reshape(*lead, h, HEAD_DIM)
    bm = xbc[..., d_in : d_in + g * n].reshape(*lead, g, n).astype(jnp.float32)
    cm = xbc[..., d_in + g * n :].reshape(*lead, g, n).astype(jnp.float32)
    return xs, bm, cm


def gated_rmsnorm(y: jax.Array, z: jax.Array, w: jax.Array, *, groups: int = 1,
                  eps: float = 1e-6) -> jax.Array:
    """RMSNorm of y * silu(z) over each of ``groups`` equal slices of the
    last axis, times ``w``; fp32 out."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    gs = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    gs = gs * jax.lax.rsqrt(jnp.mean(gs * gs, axis=-1, keepdims=True) + eps)
    return gs.reshape(g.shape) * w.astype(jnp.float32)


def _gate_out(cfg, p: dict, y: jax.Array, z: jax.Array) -> jax.Array:
    """Gated RMSNorm then down-projection.  y, z: (B, S, d_in)."""
    g = gated_rmsnorm(y, z, p["norm_w"], groups=ssm_groups(cfg),
                      eps=getattr(cfg, "ssm_norm_eps", 1e-6))
    return jnp.einsum("bse,ed->bsd", g.astype(z.dtype), p["out_proj"]).astype(z.dtype)


def mamba_forward(
    cfg, p: dict, x: jax.Array, *, chunk: int = DEFAULT_CHUNK,
    use_pallas: bool = False, interpret: bool = False,
) -> jax.Array:
    """Full-sequence mixer (train / prefill).  x: (B, S, d) -> (B, S, d).

    in_proj -> causal conv + SiLU -> SSD (``kernels/ssm_scan``, on the
    deployment's execution knob) -> + D x -> gated RMSNorm -> out_proj,
    under the ``seifer.mamba`` name scope."""
    with jax.named_scope("seifer.mamba"):
        b, s, _ = x.shape
        d_in = ssm_dims(cfg)[0]
        z, xbc, dt = _split_proj(cfg, p, x)
        xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
        xs, bm, cm = _split_xbc(cfg, xbc)
        y = ssd_chunked(xs, bm, cm, dt, -jnp.exp(p["A_log"]), chunk=min(chunk, s),
                        use_pallas=use_pallas, interpret=interpret)
        y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
        return _gate_out(cfg, p, y.reshape(b, s, d_in), z)


def mamba_init_cache(cfg, batch: int) -> dict:
    d_in, h, n = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, _conv_dim(cfg)), jnp.bfloat16),
        "ssm": jnp.zeros((batch, h, HEAD_DIM, n), jnp.float32),
    }


def mamba_step(cfg, p: dict, cache: dict, x: jax.Array) -> tuple[dict, jax.Array]:
    """Single decode step.  x: (B, 1, d).  Returns (cache', y (B, 1, d))."""
    b = x.shape[0]
    d_in, h, n = ssm_dims(cfg)
    z, xbc, dt = _split_proj(cfg, p, x)  # (B,1,*)
    window = jnp.concatenate([cache["conv"], xbc.astype(jnp.bfloat16)], axis=1)
    conv_out = (
        jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
        + p["conv_b"].astype(jnp.float32)
    )
    xbc1 = jax.nn.silu(conv_out)  # (B, conv_dim)
    xs, bm, cm = _split_xbc(cfg, xbc1)
    xs = xs.astype(jnp.float32)
    # each head reads its group's B and C: (B, G, N) -> (B, H, N)
    bm = jnp.repeat(bm, h // ssm_groups(cfg), axis=1)
    cm = jnp.repeat(cm, h // ssm_groups(cfg), axis=1)
    a = -jnp.exp(p["A_log"])
    dt1 = dt[:, 0]  # (B,H)
    decay = jnp.exp(dt1 * a)  # (B,H)
    hstate = cache["ssm"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bhn,bhd->bhdn", dt1, bm, xs
    )
    y = jnp.einsum("bhn,bhdn->bhd", cm, hstate) + xs * p["D"][None, :, None]
    out = _gate_out(cfg, p, y.reshape(b, 1, d_in), z)
    return {"conv": window[:, 1:], "ssm": hstate}, out
