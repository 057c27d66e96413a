"""Mamba2-2.7B: seeded weights and the plain float32 reference.

The reference follows the published model (state-spaces/mamba2-2.7b and the
Mamba2 paper, arXiv:2405.21060) in straightforward ``jax.numpy`` at float32
and ``Precision.HIGHEST``: embedding, 64 pre-norm residual blocks, final
RMSNorm and the tied head.  Each block's mixer is in_proj -> depthwise causal
conv + SiLU -> SSD -> + D x -> gated RMSNorm -> out_proj.  The SSD is the
paper's own minimal listing (``ssd_minimal_discrete``: quadratic form inside
256-step chunks, states passed between chunks by a segment-sum decay), not a
scan.  It imports nothing of the program under test.

``forward`` takes ``dot``, the matrix product of every projection: float32 at
HIGHEST for the reference, or ``dot_fp8`` (both operands rounded to
float8_e4m3 with a per-tensor scale) for the control, the precision one
step below the configuration's bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    m = cfg["pad_vocab_size_multiple"]
    return {
        "d": d, "d_in": d_in, "h": d_in // cfg["headdim"], "p": cfg["headdim"],
        "n": cfg["d_state"], "k": cfg["d_conv"],
        "conv_dim": d_in + 2 * cfg["ngroups"] * cfg["d_state"],
        "proj": 2 * d_in + 2 * cfg["ngroups"] * cfg["d_state"] + d_in // cfg["headdim"],
        "vocab": -(-cfg["vocab_size"] // m) * m,
    }


def init_weights(cfg: dict, words: list[int]) -> dict:
    """Every weight in bf16, made on the device in one jitted call."""
    z = dims(cfg)
    n_layer = cfg["n_layer"]

    def make(key):
        def normal(k, shape, std):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)

        k_embed, k_layers = jax.random.split(key)
        layers = []
        for i in range(n_layer):
            ks = jax.random.split(jax.random.fold_in(k_layers, i), 6)
            a = jax.random.uniform(ks[4], (z["h"],), jnp.float32, 1.0, 16.0)
            dt = jnp.exp(jax.random.uniform(ks[5], (z["h"],), jnp.float32,
                                            np.log(1e-3), np.log(1e-1)))
            dt = jnp.maximum(dt, 1e-4)
            layers.append({
                "norm": jnp.ones((z["d"],), jnp.bfloat16),
                "in_proj": normal(ks[0], (z["d"], z["proj"]), z["d"] ** -0.5),
                "conv_w": normal(ks[1], (z["k"], z["conv_dim"]), 12 ** -0.5),
                "conv_b": normal(ks[2], (z["conv_dim"],), 12 ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(a),
                "D": jnp.ones((z["h"],), jnp.float32),
                "norm_w": jnp.ones((z["d_in"],), jnp.bfloat16),
                "out_proj": normal(ks[3], (z["d_in"], z["d"]),
                                   z["d_in"] ** -0.5 / np.sqrt(n_layer)),
            })
        return {
            "embed": normal(k_embed, (z["vocab"], z["d"]), 0.02),
            "norm_f": jnp.ones((z["d"],), jnp.bfloat16),
            "layers": layers,
        }

    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    return jax.jit(make)(key)


def flops_per_request(cfg: dict, seq: int) -> float:
    """Model FLOPs of one prompt: the projections, the causal conv, the SSD
    at the published chunk (its four block products), and the head at the
    last position."""
    z = dims(cfg)
    q = min(cfg["chunk_size"], seq)
    chunks = seq // q
    proj = 2 * seq * z["d"] * z["proj"] + 2 * seq * z["d_in"] * z["d"]
    conv = 2 * seq * z["k"] * z["conv_dim"]
    ssd = chunks * (2 * q * q * z["n"] + 2 * q * q * z["h"] * z["p"]
                    + 4 * q * z["n"] * z["h"] * z["p"])
    return float(cfg["n_layer"] * (proj + conv + ssd) + 2 * z["d"] * z["vocab"])


# ---------------------------------------------------------------------------
# products: the reference's and the control's
# ---------------------------------------------------------------------------

def dot_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def dot_fp8(spec: str, a, b):
    return jnp.einsum(spec, _fp8(a.astype(jnp.float32)), _fp8(b.astype(jnp.float32)),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] below the diagonal,
    -inf above it."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd_minimal(x, a, b, c, block: int):
    """The Mamba2 paper's minimal SSD.  x (B,L,H,P) already times dt,
    a (B,L,H) = dt * A, b and c (B,L,N) for the single group."""
    bs, length, h, p = x.shape
    n = b.shape[-1]
    nc = length // block
    x = x.reshape(bs, nc, block, h, p)
    b = b.reshape(bs, nc, block, n)
    c = c.reshape(bs, nc, block, n)
    a = jnp.moveaxis(a.reshape(bs, nc, block, h), 3, 1)  # (B,H,C,L)
    a_cs = jnp.cumsum(a, axis=-1)
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    # diagonal blocks: the quadratic form inside each chunk
    decay = jnp.exp(segsum(a))  # (B,H,C,L,S)
    cb = ein("bcln,bcsn->bcls", c, b)
    y_diag = ein("bhcls,bcshp->bclhp", decay * cb[:, None], x)
    # each chunk's final state, then the states passed between chunks
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)  # (B,H,C,L)
    states = ein("bcln,bhcl,bclhp->bchpn", b, decay_states, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = ein("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bs, length, h, p)


def block_forward(cfg: dict, lw: dict, x, dot):
    """One residual block in float32.  x (B, L, d)."""
    z = dims(cfg)
    eps = cfg["norm_epsilon"]
    lw = jax.tree.map(lambda t: t.astype(jnp.float32), lw)
    h = rmsnorm(x, lw["norm"], eps)
    zxbcdt = dot("bld,de->ble", h, lw["in_proj"])
    zg = zxbcdt[..., :z["d_in"]]
    xbc = zxbcdt[..., z["d_in"]:z["d_in"] + z["conv_dim"]]
    dt = jax.nn.softplus(zxbcdt[..., z["d_in"] + z["conv_dim"]:] + lw["dt_bias"])
    k = z["k"]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + xbc.shape[1]] * lw["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + lw["conv_b"])
    xs = xbc[..., :z["d_in"]].reshape(*xbc.shape[:2], z["h"], z["p"])
    b = xbc[..., z["d_in"]:z["d_in"] + z["n"]]
    c = xbc[..., z["d_in"] + z["n"]:]
    a = -jnp.exp(lw["A_log"])
    y = ssd_minimal(xs * dt[..., None], dt * a, b, c, min(cfg["chunk_size"], x.shape[1]))
    y = (y + xs * lw["D"][:, None]).reshape(*x.shape[:2], z["d_in"])
    g = y * jax.nn.silu(zg)
    g = rmsnorm(g, lw["norm_w"], eps)
    return x + dot("ble,ed->bld", g, lw["out_proj"])


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key, dot):
    cfg = dict(cfg_key)
    blk = jax.jit(lambda lw, x: block_forward(cfg, lw, x, dot))

    def head(embed, norm_f, x):
        h = rmsnorm(x[:, -1], norm_f.astype(jnp.float32), cfg["norm_epsilon"])
        return dot("bd,vd->bv", h, embed)

    return blk, jax.jit(head)


def forward(cfg: dict, weights: dict, tokens, dot=dot_f32):
    """Next-token logits at the last position, float32, layer by layer.
    tokens (B, L) int32 -> (B, vocab_padded)."""
    key = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))
    blk, head = _jitted(key, dot)
    x = weights["embed"][tokens].astype(jnp.float32)
    for lw in weights["layers"]:
        x = blk(lw, x)
    return head(weights["embed"], weights["norm_f"], x)
