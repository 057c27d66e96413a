"""Mixture-of-Experts FFN layers.

Two layers live here:

* ``moe_mlp``: softmax top-k routing with capacity-based GShard dispatch,
  used by the LM side stack (``models/lm.py``).
* ``held_expert_moe``: the layer of one chip under expert parallelism.  It
  is told which experts it holds, routes every token over all of the
  router's experts (sigmoid scores, a selection bias, top-k, renormalised
  and scaled weights, as DeepSeek-V3 and Nemotron-H route), and computes,
  dropping no token, its held experts' part of the result through the
  grouped matmul of ``kernels/moe_gmm``, plus the shared expert.  What the
  experts held elsewhere add is left out: on one chip the layer runs without
  its exchange.

The rest of this docstring is ``moe_mlp``'s.

The dispatch/combine einsum formulation lowers cleanly under GSPMD: expert
weights are sharded over the "model" axis (expert parallelism), tokens over
"data"; the combine contraction over the expert axis produces the EP
all-reduce.  Dispatch-tensor memory is bounded by the ``group_size`` knob
(tokens are routed within groups): dispatch is (G, Sg, E, C) with
C = ceil(Sg * top_k * capacity_factor / E), so bytes scale with Sg, not S.

Tokens beyond expert capacity are dropped (classic Switch/GShard semantics);
the auxiliary load-balancing loss keeps drop rates low in training.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import gmm
from repro.models.common import dense_init
from repro.models.layers import mlp

RELU2 = SimpleNamespace(mlp_kind="relu2")

DEFAULT_GROUP = 512


def init_moe(cfg, key: jax.Array) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32, scale=d**-0.5),
        "w_gate": dense_init(ks[1], (e, d, f)),
        "w_up": dense_init(ks[2], (e, d, f)),
        "w_down": dense_init(ks[3], (e, f, d), scale=f**-0.5),
    }


def _capacity(group: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(-(-group * top_k * cf // n_experts))  # ceil
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def route(cfg, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router probabilities and top-k selection.  x: (..., d) bf16.

    Returns (probs (..., E) f32, top_p (..., k) f32, top_e (..., k) i32).
    Top-k probabilities are renormalized (Mixtral-style).
    """
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def moe_mlp(
    cfg, p: dict, x: jax.Array, *, group_size: int = DEFAULT_GROUP
) -> tuple[jax.Array, jax.Array]:
    """Top-k MoE FFN.  x: (B, S, d).  Returns (y, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    g_sz = min(group_size, t)
    if t % g_sz:
        g_sz = t  # fall back to one group (smoke-test sizes)
    g = t // g_sz
    xg = x.reshape(g, g_sz, d)

    probs, top_p, top_e = route(cfg, p, xg)  # (G,Sg,E) (G,Sg,k) (G,Sg,k)
    cap = _capacity(g_sz, k, e, cfg.moe_capacity_factor)

    # --- position of each (token, slot) within its expert's capacity ------
    onehot_e = jax.nn.one_hot(top_e, e, dtype=jnp.float32)  # (G,Sg,k,E)
    flat = onehot_e.reshape(g, g_sz * k, e)
    pos_flat = jnp.cumsum(flat, axis=1) - flat  # (G,Sg*k,E)
    pos = (pos_flat.reshape(g, g_sz, k, e) * onehot_e).sum(-1)  # (G,Sg,k)
    keep = (pos < cap).astype(jnp.float32)

    onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    # dispatch (G,Sg,E,C): 1 where token s goes to slot c of expert e
    dispatch = jnp.einsum("gske,gskc,gsk->gsec", onehot_e, onehot_c, keep)
    combine = jnp.einsum("gske,gskc,gsk->gsec", onehot_e, onehot_c, keep * top_p)

    # --- expert compute -----------------------------------------------------
    xin = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), x.reshape(g, g_sz, d))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, p["w_gate"])) * jnp.einsum(
        "gecd,edf->gecf", xin, p["w_up"]
    )
    out = jnp.einsum("gecf,efd->gecd", h.astype(x.dtype), p["w_down"])
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(jnp.float32), out.astype(jnp.float32))

    # --- load-balancing auxiliary loss (Switch Eq. 4) ------------------------
    frac_tokens = onehot_e.mean(axis=(1, 2))  # (G,E) fraction routed
    frac_probs = probs.mean(axis=1)  # (G,E)
    aux = e * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# The held experts' share, dropless (expert parallelism)
# ---------------------------------------------------------------------------

def sigmoid_route(x: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int,
                  scaling: float) -> tuple[jax.Array, jax.Array]:
    """Routing over all of the router's experts.  x (T, d).

    Logits in f32 at HIGHEST precision, sigmoid scores; the top ``top_k``
    of scores + ``bias`` are chosen (``jax.lax.top_k``: on a tie the lower
    index first); their unbiased scores, renormalised to sum to one and
    times ``scaling``, weight them.  Returns (experts (T, k) int32,
    weights (T, k) f32)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, w / (w.sum(-1, keepdims=True) + 1e-20) * scaling


def sort_by_expert(picks: jax.Array, n_experts: int):
    """A stable counting sort of ``picks`` (m,) int32 by expert.

    Returns ``order`` (m,): the picks' indices sorted by expert, ties in
    index order; ``dest`` (m,): each pick's row in that order (the inverse
    of ``order``); and ``sizes`` (n_experts,) int32.  A pick's row is its
    expert's first row plus the picks of that expert before it, counted in
    blocks of 128 by a strictly lower-triangular matmul of the one-hot picks
    (0/1 in bf16, sums in f32: exact), so no sort runs."""
    m = picks.shape[0]
    blk = math.gcd(m, 128)
    onehot = jax.nn.one_hot(picks, n_experts, dtype=jnp.bfloat16).reshape(m // blk, blk, n_experts)
    earlier = jnp.tril(jnp.ones((blk, blk), jnp.bfloat16), -1)
    within = jnp.einsum("ij,bjn->bin", earlier, onehot, preferred_element_type=jnp.float32)
    per_block = onehot.sum(axis=1, dtype=jnp.float32)
    before = jnp.cumsum(per_block, axis=0) - per_block  # in earlier blocks
    sizes = per_block.sum(axis=0)
    first = jnp.cumsum(sizes) - sizes
    rank = jnp.sum((within + before[:, None]) * onehot, axis=-1).reshape(m)
    dest = (first[picks] + rank).astype(jnp.int32)
    order = jnp.zeros((m,), jnp.int32).at[dest].set(
        jnp.arange(m, dtype=jnp.int32), unique_indices=True)
    return order, dest, sizes.astype(jnp.int32)


def held_expert_moe(p: dict, x: jax.Array, *, top_k: int, scaling: float,
                    first_expert: int = 0, use_pallas: bool = False,
                    interpret: bool = False) -> jax.Array:
    """The part of a routed-expert layer that experts ``first_expert ..
    first_expert + held - 1`` give, plus the shared expert.  x (..., d).

    ``p``: ``router`` (d, E) and ``bias`` (E,) over all E experts, ``w_up``
    (held, d, f) and ``w_down`` (held, f, d) of the held experts (relu^2,
    no gate), and ``shared``, the shared expert's ``mlp`` (kind relu2).  Every
    (token, pick) pair is sorted by expert (``sort_by_expert``), the held
    experts' rows go through ``kernels.moe_gmm.gmm``, relu^2 and the down
    projection, each row is un-sorted back to its pick and weighted, and a
    token's picks are summed.  Nothing is dropped: the buffers hold every
    pick, so even all picks on one held expert are computed."""
    with jax.named_scope("seifer.moe"):
        shape = x.shape
        xt = x.reshape(-1, shape[-1])
        t = xt.shape[0]
        experts, w = sigmoid_route(xt, p["router"], p["bias"], top_k=top_k, scaling=scaling)
        order, dest, sizes = sort_by_expert(experts.reshape(-1), p["router"].shape[1])
        knob = dict(group_offset=first_expert, out_dtype=x.dtype,
                    use_pallas=use_pallas, interpret=interpret)
        h = gmm(xt[order // top_k], p["w_up"], sizes, **knob)
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
        y = gmm(h, p["w_down"], sizes, **knob)
        # pick i of token i // top_k is row dest[i] of y
        y = y[dest].astype(jnp.float32) * w.reshape(-1, 1)
        routed = y.reshape(t, top_k, -1).sum(axis=1)
        shared = mlp(RELU2, p["shared"], xt).astype(jnp.float32)
        return (routed + shared).astype(x.dtype).reshape(shape)
