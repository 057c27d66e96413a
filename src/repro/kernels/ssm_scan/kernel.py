"""Pallas TPU kernel: chunked SSD (Mamba2) selective-state scan.

Grid (B*H, nc): the chunk dim is LAST, so TPU executes it sequentially and
the (dh, N) recurrent state lives in VMEM scratch across a head's chunks
(the same scratch-carry idiom as the flash-attention kernel).  Per step the
MXU sees three small matmuls: C@B^T (Q,N)x(N,Q), scores@x (Q,Q)x(Q,dh) and
x^T@(B*decay) (dh,Q)x(Q,N).  VMEM at Q=128, N=64, dh=64: inputs ~100 KiB,
L-matrix 64 KiB f32, state 16 KiB -- trivially resident.

Layout: the wrapper folds the head into the leading axis, so every block's
last two dims are either (Q, dh)/(Q, N) or a per-position vector as a
(Q, 1) column or a (1, Q) row -- the shapes the TPU lowering tiles.  B and C
come in G groups (Mamba2's ``ngroups``) folded the same way, as (B*G, S, N):
head h of a sequence reads group h // (H/G).  One group keeps them (B, S, N).  The
per-chunk cumulative decays are precomputed outside (one cumsum) and handed
in both orientations, so the kernel never transposes a vector and has no
sequential math inside a chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xs_ref, bm_ref, cm_ref, dt_row_ref, cum_row_ref, cum_col_ref,
                dt_col_ref, y_ref, state_scr, *, q: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xs = xs_ref[0].astype(jnp.float32)  # (Q, dh)
    bm = bm_ref[0].astype(jnp.float32)  # (Q, N)
    cm = cm_ref[0].astype(jnp.float32)  # (Q, N)
    dt_row = dt_row_ref[0].astype(jnp.float32)  # (1, Q)
    cum_row = cum_row_ref[0].astype(jnp.float32)  # (1, Q)
    cum_col = cum_col_ref[0].astype(jnp.float32)  # (Q, 1)
    dt_col = dt_col_ref[0].astype(jnp.float32)  # (Q, 1)
    cum_last = cum_row[:, q - 1:]  # (1, 1): the chunk's total decay

    # intra-chunk: masked decay-weighted attention over the chunk
    ldiff = cum_col - cum_row  # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (q, q), 1
    )
    lmat = jnp.where(tri, jnp.exp(ldiff), 0.0)
    gbc = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q, Q)
    scores = gbc * lmat * dt_row
    y = jax.lax.dot_general(scores, xs, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, dh)

    # inter-chunk: readout of the carried state
    state = state_scr[...]  # (dh, N)
    y += jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * jnp.exp(cum_col)

    # state update
    decay_out = jnp.exp(cum_last - cum_col) * dt_col  # (Q, 1)
    contrib = jax.lax.dot_general(
        xs, bm * decay_out, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (dh, N)
    state_scr[...] = state * jnp.exp(cum_last) + contrib

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_chunked_tpu(xs, bm, cm, dt, a, *, chunk: int = 128, interpret: bool = False):
    """xs (B,S,H,dh), bm/cm (B,S,N) or (B,S,G,N), dt (B,S,H), a (H,) ->
    y (B,S,H,dh) f32."""
    b, s, h, dh = xs.shape
    n = bm.shape[-1]
    g = bm.shape[2] if bm.ndim == 4 else 1
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    hpg = h // g  # heads per group
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} must divide chunk {q}")

    def groups_first(t):  # (B, S, G, N) -> (B*G, S, N); one group: (B, S, N)
        return jnp.moveaxis(t, 2, 1).reshape(b * g, s, n) if t.ndim == 4 else t

    bm, cm = groups_first(bm), groups_first(cm)
    nc = s // q
    cum = jnp.cumsum((dt * a).reshape(b, nc, q, h), axis=2).reshape(b, s, h)

    def heads_first(t):  # (B, S, H, ...) -> (B*H, S, ...)
        return jnp.moveaxis(t, 2, 1).reshape(b * h, s, *t.shape[3:])

    xs_h = heads_first(xs)  # (BH, S, dh)
    dt_col = heads_first(dt)[..., None]  # (BH, S, 1)
    cum_col = heads_first(cum)[..., None]  # (BH, S, 1)
    dt_row = jnp.swapaxes(dt_col, 1, 2)  # (BH, 1, S)
    cum_row = jnp.swapaxes(cum_col, 1, 2)  # (BH, 1, S)

    row = pl.BlockSpec((1, 1, q), lambda bh, ci: (bh, 0, ci))
    col = pl.BlockSpec((1, q, 1), lambda bh, ci: (bh, ci, 0))
    proj = pl.BlockSpec((1, q, n), lambda bh, ci: (bh // hpg, ci, 0))
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, q=q),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, q, dh), lambda bh, ci: (bh, ci, 0)),
            proj, proj, row, row, col, col,
        ],
        out_specs=pl.BlockSpec((1, q, dh), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, n), jnp.float32)],
        interpret=interpret,
    )(xs_h, bm, cm, dt_row, cum_row, cum_col, dt_col)
    return jnp.moveaxis(y.reshape(b, h, s, dh), 1, 2)
