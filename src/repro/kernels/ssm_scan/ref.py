"""Pure-jnp oracle for the chunked SSD (Mamba2) scan.

Inputs are the post-projection, post-conv tensors of one mamba layer:
  xs  (B, S, H, dh)     state inputs (bf16/f32)
  bm  (B, S, G, N)      input projections B_t in G groups (f32); (B, S, N)
                        for one group
  cm  like bm           output projections C_t (f32)
  dt  (B, S, H)         softplus'd step sizes (f32)
  a   (H,)              negative decay rates (f32)

Head h reads group h // (H / G).  Output: y (B, S, H, dh) f32 with
y_t = sum_{s<=t} C_t^T (prod exp(dt A)) dt_s B_s x_s, and the final state
(B, H, dh, N).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_ref(xs, bm, cm, dt, a, *, chunk: int = 64):
    b, s, h, dh = xs.shape
    if bm.ndim == 3:
        bm, cm = bm[:, :, None], cm[:, :, None]
    g, n = bm.shape[2:]
    hg = h // g  # heads per group
    q = min(chunk, s)
    assert s % q == 0 and h % g == 0
    nc = s // q
    da = dt * a  # (B,S,H)
    # heads split as (G, H/G): every einsum below contracts within a group
    xs_c = xs.reshape(b, nc, q, g, hg, dh).astype(jnp.float32)
    bm_c = bm.reshape(b, nc, q, g, n)
    cm_c = cm.reshape(b, nc, q, g, n)
    dt_c = dt.reshape(b, nc, q, g, hg)
    cum = jnp.cumsum(da.reshape(b, nc, q, g, hg), axis=2)

    def step(hstate, inp):
        xs_k, bm_k, cm_k, dt_k, cum_k = inp
        ldiff = cum_k[:, :, None] - cum_k[:, None, :]  # (B,Q,S,G,hg)
        mask = jnp.tril(jnp.ones((q, q), bool))
        lmat = jnp.where(mask[None, :, :, None, None], jnp.exp(ldiff), 0.0)
        gbc = jnp.einsum("btgn,bsgn->btsg", cm_k, bm_k)
        scores = gbc[..., None] * lmat * dt_k[:, None]
        y_intra = jnp.einsum("btsgh,bsghd->btghd", scores, xs_k)
        y_inter = (jnp.einsum("btgn,bghdn->btghd", cm_k, hstate)
                   * jnp.exp(cum_k)[..., None])
        decay_out = jnp.exp(cum_k[:, -1:] - cum_k)
        contrib = jnp.einsum("bsgh,bsgn,bsghd->bghdn", decay_out * dt_k, bm_k, xs_k)
        h_new = hstate * jnp.exp(cum_k[:, -1])[..., None, None] + contrib
        return h_new, y_intra + y_inter

    h0 = jnp.zeros((b, g, hg, dh, n), jnp.float32)
    inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (xs_c, bm_c, cm_c, dt_c, cum))
    hT, y = jax.lax.scan(step, h0, inputs)
    return jnp.moveaxis(y, 0, 1).reshape(b, s, h, dh), hT.reshape(b, h, dh, n)
