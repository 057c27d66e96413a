"""The expert-parallel MoE layer and the grouped Mamba-2 pieces it serves
beside: the grouped matmul over held experts (Pallas interpret vs jnp),
the share of the experts each chip computes, dropless routing, the grouped
gated norm and the grouped mixer's decode recurrence."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm import gmm
from repro.models import moe, ssm

# (m, k, n, group sizes over all experts, held, first held expert)
GMM_CASES = {
    "offset_and_empty_groups": (64, 200, 136, [10, 0, 14, 0, 20, 8, 12, 0], 3, 2),
    "irregular_k_n_all_held": (128, 2688 // 8, 1856 // 8, [30, 2, 0, 96], 4, 0),
    "last_experts_one_row_each": (32, 128, 128, [29, 0, 1, 1, 1], 3, 2),
    "no_row_held": (32, 64, 64, [16, 16, 0, 0], 2, 2),
}


@pytest.mark.parametrize("case", sorted(GMM_CASES))
def test_gmm_kernel_matches_jnp(case):
    m, k, n, sizes, held, first = GMM_CASES[case]
    assert sum(sizes) == m
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    lhs = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (held, k, n)).astype(jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = gmm(lhs, rhs, sizes, group_offset=first)
    got = gmm(lhs, rhs, sizes, group_offset=first, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)
    # rows of experts not held are zero; a held expert's row is its product
    ends = np.cumsum(np.asarray(sizes))
    expert = np.searchsorted(ends, np.arange(m), side="right")
    mine = (expert >= first) & (expert < first + held)
    assert not np.asarray(got)[~mine].any()
    for r in np.flatnonzero(mine)[:3]:
        row = np.asarray(lhs[r], np.float32) @ np.asarray(rhs[expert[r] - first], np.float32)
        np.testing.assert_allclose(np.asarray(got)[r], row, rtol=1e-4, atol=1e-3)


D, F, FS, E, K = 64, 32, 48, 8, 2


def _experts(key, held=E):
    ks = jax.random.split(key, 6)

    def w(k, shape):
        return (jax.random.normal(k, shape) * 0.2).astype(jnp.bfloat16)

    return {
        "router": w(ks[0], (D, E)), "bias": jnp.zeros((E,), jnp.float32),
        "w_up": w(ks[1], (held, D, F)), "w_down": w(ks[2], (held, F, D)),
        "shared": {"w_up": w(ks[3], (D, FS)), "w_down": w(ks[4], (FS, D))},
    }


def _uncut(p, x):
    """Every expert of the layer, dense and in f32: each token's weighted
    relu^2 experts plus the shared expert."""
    f32 = lambda t: np.asarray(t, np.float32)  # noqa: E731
    xt = f32(x).reshape(-1, D)
    scores = 1 / (1 + np.exp(-(xt @ f32(p["router"]))))
    top = np.argsort(-(scores + f32(p["bias"])), axis=-1, kind="stable")[:, :K]
    w = np.take_along_axis(scores, top, -1)
    w = w / w.sum(-1, keepdims=True) * 2.5
    relu2 = lambda t: np.square(np.maximum(t, 0))  # noqa: E731
    out = relu2(xt @ f32(p["shared"]["w_up"])) @ f32(p["shared"]["w_down"])
    for e in range(E):
        coef = (w * (top == e)).sum(-1)
        out += coef[:, None] * (relu2(xt @ f32(p["w_up"][e])) @ f32(p["w_down"][e]))
    return out.reshape(x.shape)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_expert_shares_add_up_to_the_uncut_layer(use_pallas):
    """Each chip's share (held experts, shared expert) computed for every
    share of the experts: the routed parts, with the shared expert counted
    once, add up to the whole layer."""
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 16, D))).astype(jnp.bfloat16)
    p = _experts(jax.random.PRNGKey(2))
    held = 4
    parts = []
    for first in range(0, E, held):
        share = dict(p, w_up=p["w_up"][first:first + held], w_down=p["w_down"][first:first + held])
        parts.append(np.asarray(moe.held_expert_moe(
            share, x, top_k=K, scaling=2.5, first_expert=first, use_pallas=use_pallas,
            interpret=True), np.float32))
    shared = np.asarray(moe.held_expert_moe(
        dict(p, w_up=p["w_up"][:0], w_down=p["w_down"][:0]), x, top_k=K, scaling=2.5,
        first_expert=E), np.float32)
    total = sum(parts) - (len(parts) - 1) * shared
    want = _uncut(p, x)
    assert np.linalg.norm(total - want) / np.linalg.norm(want) < 2e-2


def test_no_pick_is_dropped_when_one_held_expert_takes_every_token():
    """A bias that sends every token's first pick to expert 0: the layer
    computes all of them (no capacity), as the dense sum does."""
    x = (jax.random.normal(jax.random.PRNGKey(3), (1, 64, D))).astype(jnp.bfloat16)
    p = _experts(jax.random.PRNGKey(4))
    p["bias"] = p["bias"].at[0].set(10.0).at[1].set(5.0)
    experts, _ = moe.sigmoid_route(x.reshape(-1, D), p["router"], p["bias"], top_k=K, scaling=2.5)
    assert (np.asarray(experts) == [0, 1]).all()
    share = dict(p, w_up=p["w_up"][:2], w_down=p["w_down"][:2])
    got = np.asarray(moe.held_expert_moe(share, x, top_k=K, scaling=2.5, use_pallas=True,
                                         interpret=True), np.float32)
    want = _uncut(p, x)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-2


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_grouped_gated_norm_is_a_norm_per_group(groups):
    y = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 64))
    z = jax.random.normal(jax.random.PRNGKey(6), (3, 5, 64))
    w = jax.random.normal(jax.random.PRNGKey(7), (64,))
    got = ssm.gated_rmsnorm(y, z, w, groups=groups, eps=1e-5)
    g = np.asarray(y) * np.asarray(jax.nn.silu(z))
    size = 64 // groups
    want = np.concatenate([
        g[..., i * size:(i + 1) * size]
        / np.sqrt(np.mean(g[..., i * size:(i + 1) * size] ** 2, -1, keepdims=True) + 1e-5)
        for i in range(groups)], -1) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_relu2_mlp_squares_the_relu():
    x = jax.random.normal(jax.random.PRNGKey(8), (4, D)).astype(jnp.bfloat16)
    p = _experts(jax.random.PRNGKey(9))["shared"]
    got = moe.mlp(moe.RELU2, p, x)
    h = np.square(np.maximum(np.asarray(x, np.float32) @ np.asarray(p["w_up"], np.float32), 0))
    want = h @ np.asarray(p["w_down"], np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=3e-2, atol=3e-2)


def test_grouped_mamba_forward_matches_steps():
    """d_inner from heads x head size (not expand x d), B/C in 2 groups,
    norm eps 1e-5: the chunked mixer and the decode recurrence agree."""
    cfg = SimpleNamespace(d_model=48, ssm_expand=2, ssm_heads=4, ssm_groups=2, ssm_state=16,
                          ssm_conv_width=4, ssm_norm_eps=1e-5)
    assert ssm.ssm_dims(cfg) == (256, 4, 16)
    p = ssm.init_mamba(cfg, jax.random.PRNGKey(0))
    assert p["in_proj"].shape == (48, 2 * 256 + 2 * 2 * 16 + 4)
    b, s = 2, 8
    x = (jax.random.normal(jax.random.PRNGKey(1), (b, s, 48)) * 0.5).astype(jnp.bfloat16)
    y_par = ssm.mamba_forward(cfg, p, x, chunk=4)
    cache = ssm.mamba_init_cache(cfg, b)
    outs = []
    for t in range(s):
        cache, y = ssm.mamba_step(cfg, p, cache, x[:, t:t + 1])
        outs.append(y)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(jnp.concatenate(outs, 1), np.float32),
                               atol=3e-2, rtol=3e-2)
    y_pal = ssm.mamba_forward(cfg, p, x, chunk=4, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32), np.asarray(y_par, np.float32),
                               atol=1e-2, rtol=1e-2)
