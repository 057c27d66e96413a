"""Nemotron-3-Nano-30B-A3B, the share of one chip of eight under expert
parallelism: seeded weights and the plain float32 reference.

The reference follows the published model (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16, ``config.json`` and ``modeling_nemotron_h.py``) in
straightforward ``jax.numpy`` at float32 and ``Precision.HIGHEST``: the
embedding, then one block per letter of ``hybrid_override_pattern``, each
RMSNorm -> mixer -> residual add, then the final RMSNorm and the untied head
at the last ``answer_positions`` positions.  The mixers:

- ``M``, Mamba-2: in_proj -> depthwise causal conv + SiLU -> SSD with B and
  C in ``n_groups`` groups (head h reads group h // (heads / groups)) ->
  + D x -> RMSNorm of y * silu(z) over groups of d_inner / n_groups
  channels -> out_proj.  d_inner is heads x head size.  The SSD is the
  Mamba-2 paper's minimal listing (quadratic form inside chunks, states
  passed between chunks by a segment-sum decay), written out here per group.
- ``*``, attention: q/k/v projections, causal grouped-query softmax
  attention with no positional encoding, o_proj.
- ``E``, routed experts: f32 router logits, sigmoid scores, the top
  ``num_experts_per_tok`` of scores + ``e_score_correction_bias`` chosen
  (``jax.lax.top_k``: on a tie the lower index first), their unbiased scores
  renormalised (+1e-20 in the denominator) and times
  ``routed_scaling_factor``; the held experts' part is a loop over the held
  experts, each a relu^2 MLP applied to every token and masked by the
  token's weight for it (no sorting, no kernel); plus the shared relu^2
  expert.  Experts not held add nothing, as on the chip.

It imports nothing of the program under test.  ``forward`` casts one layer's
weights to float32 at a time, so it fits beside the bf16 weights once the
deployment is freed.  ``forward`` takes ``dot``, the product of every
projection: float32 at HIGHEST for the reference, ``dot_fp8`` for the
control.  ``route`` gives the reference's picks for the routing counter
(``bench/routes.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_in = h * p
    return {
        "d": cfg["hidden_size"], "d_in": d_in, "h": h, "p": p, "g": g, "n": n,
        "k": cfg["conv_kernel"], "conv_dim": d_in + 2 * g * n,
        "proj": 2 * d_in + 2 * g * n + h,
        "qh": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "held": cfg["n_routed_experts"], "experts": cfg["n_router_experts"],
        "topk": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
    }


def kinds(cfg: dict) -> str:
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise ValueError(f"pattern {pattern!r} does not give {cfg['num_hidden_layers']} "
                         f"blocks of M, E and *")
    return pattern


def init_weights(cfg: dict, words: list[int]) -> dict:
    """Every weight in bf16 (A_log, dt_bias, D and the selection bias in
    f32), made on the device in one jitted call.  The embedding, every
    projection, the router and the head N(0, 0.02), the family's
    ``initializer_range``; the projections that write to the residual stream
    further divided by sqrt(num_hidden_layers)."""
    z = dims(cfg)
    d = z["d"]
    out_scale = 0.02 / np.sqrt(cfg["num_hidden_layers"])

    def make(key):
        def normal(k, shape, std=0.02):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)

        def mamba(ks):
            a = jax.random.uniform(ks[4], (z["h"],), jnp.float32, 1.0, 16.0)
            dt = jnp.exp(jax.random.uniform(ks[5], (z["h"],), jnp.float32,
                                            np.log(cfg["time_step_min"]),
                                            np.log(cfg["time_step_max"])))
            dt = jnp.maximum(dt, cfg["time_step_floor"])
            return {
                "in_proj": normal(ks[0], (d, z["proj"])),
                "conv_w": normal(ks[1], (z["k"], z["conv_dim"]), 12 ** -0.5),
                "conv_b": normal(ks[2], (z["conv_dim"],), 12 ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(a),
                "D": jnp.ones((z["h"],), jnp.float32),
                "norm_w": jnp.ones((z["d_in"],), jnp.bfloat16),
                "out_proj": normal(ks[3], (z["d_in"], d), out_scale),
            }

        def attention(ks):
            return {
                "wq": normal(ks[0], (d, z["qh"], z["hd"])),
                "wk": normal(ks[1], (d, z["kvh"], z["hd"])),
                "wv": normal(ks[2], (d, z["kvh"], z["hd"])),
                "wo": normal(ks[3], (z["qh"], z["hd"], d), out_scale),
            }

        def moe(ks):
            return {
                "router": normal(ks[0], (d, z["experts"])),
                "bias": jnp.zeros((z["experts"],), jnp.float32),
                "w_up": normal(ks[1], (z["held"], d, z["f"])),
                "w_down": normal(ks[2], (z["held"], z["f"], d), out_scale),
                "shared": {"w_up": normal(ks[3], (d, z["fs"])),
                           "w_down": normal(ks[4], (z["fs"], d), out_scale)},
            }

        make_mixer = {"M": mamba, "*": attention, "E": moe}
        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for i, kind in enumerate(kinds(cfg)):
            ks = jax.random.split(jax.random.fold_in(k_layers, i), 6)
            layers.append(dict(make_mixer[kind](ks), norm=jnp.ones((d,), jnp.bfloat16)))
        return {
            "embed": normal(k_embed, (z["vocab"], d)),
            "norm_f": jnp.ones((d,), jnp.bfloat16),
            "lm_head": normal(k_head, (z["vocab"], d)),
            "layers": layers,
        }

    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    return jax.jit(make)(key)


def flops_by_kind(cfg: dict, seq: int) -> dict:
    """Model FLOPs of one prompt for one block of each kind, and for the head.

    Mamba: the projections, the conv and the SSD at the published chunk (per
    chunk C B^T once per group, then per head the masked scores times x, the
    state read out and updated).  Attention: the projections and causal
    attention (QK^T and PV over seq^2 / 2 pairs).  MoE: the router at its
    full width, the shared expert, and the held experts at the rows they
    expect under balanced routing, top_k x held / experts per token.  The
    head at the last ``answer_positions`` positions."""
    z = dims(cfg)
    d = z["d"]
    q = min(cfg["chunk_size"], seq)
    ssd = (seq // q) * (2 * q * q * z["n"] * z["g"]
                        + z["h"] * (2 * q * q * z["p"] + 4 * q * z["n"] * z["p"]))
    mamba = (2 * seq * d * z["proj"] + 2 * seq * z["d_in"] * d
             + 2 * seq * z["k"] * z["conv_dim"] + ssd)
    attn = (2 * seq * d * (z["qh"] + 2 * z["kvh"]) * z["hd"] + 2 * seq * z["qh"] * z["hd"] * d
            + 2 * 2 * z["qh"] * z["hd"] * seq * seq / 2)
    rows = seq * z["topk"] * z["held"] / z["experts"]
    moe = 2 * seq * d * z["experts"] + 4 * seq * d * z["fs"] + 4 * rows * d * z["f"]
    return {"M": float(mamba), "*": float(attn), "E": float(moe),
            "head": float(2 * d * z["vocab"] * cfg["answer_positions"])}


def flops_per_request(cfg: dict, seq: int) -> float:
    per = flops_by_kind(cfg, seq)
    return float(sum(per[k] for k in kinds(cfg)) + per["head"])


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
    """The Mamba-2 paper's minimal SSD with B and C in groups.  x (B,L,H,P)
    already times dt, a (B,L,H) = dt * A, b and c (B,L,G,N)."""
    bs, length, h, p = x.shape
    g, n = b.shape[2:]
    nc = length // block
    x = x.reshape(bs, nc, block, g, h // g, p)
    b = b.reshape(bs, nc, block, g, n)
    c = c.reshape(bs, nc, block, g, n)
    a = jnp.moveaxis(a.reshape(bs, nc, block, g, h // g), 2, -1)  # (B,C,G,R,L)
    a_cs = jnp.cumsum(a, axis=-1)
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    # diagonal blocks: the quadratic form inside each chunk
    decay = jnp.exp(segsum(a))  # (B,C,G,R,L,S)
    cb = ein("bclgn,bcsgn->bcgls", c, b)
    y_diag = ein("bcgrls,bcsgrp->bclgrp", decay * cb[:, :, :, None], x)
    # each chunk's final state, then the states passed between chunks
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)  # (B,C,G,R,L)
    states = ein("bclgn,bcgrl,bclgrp->bcgrpn", b, decay_states, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    last = jnp.moveaxis(a_cs[..., -1], 1, -1)  # (B,G,R,C)
    decay_chunk = jnp.exp(segsum(jnp.pad(last, ((0, 0), (0, 0), (0, 0), (1, 0)))))
    states = ein("bgrzc,bcgrpn->bzgrpn", decay_chunk, states)[:, :-1]
    y_off = ein("bclgn,bcgrpn,bcgrl->bclgrp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bs, length, h, p)


def mamba_mixer(cfg: dict, lw: dict, h, dot):
    z = dims(cfg)
    zxbcdt = dot("bld,de->ble", h, lw["in_proj"])
    zg = zxbcdt[..., :z["d_in"]]
    xbc = zxbcdt[..., z["d_in"]:z["d_in"] + z["conv_dim"]]
    dt = jax.nn.softplus(zxbcdt[..., z["d_in"] + z["conv_dim"]:] + lw["dt_bias"])
    k = z["k"]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + xbc.shape[1]] * lw["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + lw["conv_b"])
    lead = xbc.shape[:2]
    gn = z["g"] * z["n"]
    xs = xbc[..., :z["d_in"]].reshape(*lead, z["h"], z["p"])
    b = xbc[..., z["d_in"]:z["d_in"] + gn].reshape(*lead, z["g"], z["n"])
    c = xbc[..., z["d_in"] + gn:].reshape(*lead, z["g"], z["n"])
    a = -jnp.exp(lw["A_log"])
    y = ssd_minimal(xs * dt[..., None], dt * a, b, c, min(cfg["chunk_size"], h.shape[1]))
    y = (y + xs * lw["D"][:, None]).reshape(*lead, z["d_in"])
    gate = (y * jax.nn.silu(zg)).reshape(*lead, z["g"], z["d_in"] // z["g"])
    gate = rmsnorm(gate, 1.0, cfg["layer_norm_epsilon"]).reshape(*lead, z["d_in"])
    return dot("ble,ed->bld", gate * lw["norm_w"], lw["out_proj"])


def attention_mixer(cfg: dict, lw: dict, h, dot):
    z = dims(cfg)
    q = dot("bld,dhk->blhk", h, lw["wq"])
    k = dot("bld,dhk->blhk", h, lw["wk"])
    v = dot("bld,dhk->blhk", h, lw["wv"])
    b, length = h.shape[:2]
    qg = q.reshape(b, length, z["kvh"], z["qh"] // z["kvh"], z["hd"])
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg, k, precision=HIGHEST) * z["hd"] ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", w, v, precision=HIGHEST)
    return dot("blhk,hkd->bld", o.reshape(b, length, z["qh"], z["hd"]), lw["wo"])


def route(cfg: dict, lw: dict, h):
    """The reference's picks and weights for tokens h (..., d):
    (experts (..., k) int32, weights (..., k))."""
    logits = jnp.einsum("...d,de->...e", h, lw["router"].astype(jnp.float32),
                        precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + lw["bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return experts, w


def moe_mixer(cfg: dict, lw: dict, h, dot):
    z = dims(cfg)
    experts, w = route(cfg, lw, h)
    out = dot("bld,df->blf", h, lw["shared"]["w_up"])
    out = dot("blf,fd->bld", jnp.square(jax.nn.relu(out)), lw["shared"]["w_down"])
    for j in range(z["held"]):
        e = cfg["first_held_expert"] + j
        coef = jnp.sum(jnp.where(experts == e, w, 0.0), axis=-1)  # (B, L)
        y = jnp.square(jax.nn.relu(dot("bld,df->blf", h, lw["w_up"][j])))
        out = out + coef[..., None] * dot("blf,fd->bld", y, lw["w_down"][j])
    return out


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": moe_mixer}


def block_forward(cfg: dict, kind: str, lw: dict, x, dot):
    """One block in float32.  x (B, L, d)."""
    lw = jax.tree.map(lambda t: t.astype(jnp.float32), lw)
    h = rmsnorm(x, lw["norm"], cfg["layer_norm_epsilon"])
    return x + MIXERS[kind](cfg, lw, h, dot)


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key, dot):
    cfg = dict(cfg_key)
    blocks = {kind: jax.jit(functools.partial(block_forward, cfg, kind, dot=dot))
              for kind in MIXERS}

    def head(lm_head, norm_f, x):
        h = rmsnorm(x[:, -cfg["answer_positions"]:], norm_f.astype(jnp.float32),
                    cfg["layer_norm_epsilon"])
        return dot("bld,vd->blv", h, lm_head)

    return blocks, jax.jit(head)


def forward(cfg: dict, weights: dict, tokens, dot=dot_f32):
    """Next-token logits at the last ``answer_positions`` positions, float32,
    layer by layer.  tokens (B, L) int32 -> (B, answer_positions, vocab)."""
    blocks, head = _jitted(_cfg_key(cfg), dot)
    x = weights["embed"][tokens].astype(jnp.float32)
    for kind, lw in zip(kinds(cfg), weights["layers"]):
        x = blocks[kind](lw, x)
    return head(weights["lm_head"], weights["norm_f"], x)
