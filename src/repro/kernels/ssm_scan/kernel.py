"""Pallas TPU kernel: chunked SSD (Mamba2) selective-state scan.

Grid (B*H/hb, nc): each step takes one block of ``hb`` heads that share a
B/C group, for one chunk of Q positions.  The chunk dim is LAST, so TPU
executes it sequentially and the block's recurrent states, (hb*dh, N) f32,
live in VMEM scratch across its chunks (the same scratch-carry idiom as the
flash-attention kernel).  A step reads its group's B and C chunk once and
computes B C^T, the causal mask and the states' readout (hb*dh, N)x(N, Q)
once for its hb heads; per head the MXU then sees x^T@scores^T (dh,Q)x(Q,Q)
and the state update x^T@(B*w) (dh,Q)x(Q,N).  Operands and accumulation
are f32.

``hb`` follows from the shapes alone (``ssd_blocking``): the largest divisor
of the heads per group whose step fits ``VMEM_BUDGET`` -- double-buffered
blocks, the state scratch and one head's (Q, Q) temporaries, lanes padded
to 128.  At Q 128, dh 64, N 128 a step of 8 heads takes 2.4 MB, of 20
heads 5.2 MB and of 40 heads 9.9 MB; on a v5e more heads a step ran faster
(80 heads a group: 1.84 ms a call at 8, 1.30 at 20, 1.20 at 40).

Operands (7): x as (B*H, S, dh); B and C as (B*G, S, N), G groups folded
into the batch (one group keeps them (B, S, N)), head h of a sequence
reading group h // (H/G); then four per-position f32 vectors, each
(B*H/hb, hb, S), so a block is one (hb, Q) tile with positions on the
lanes: dt, the chunk-wise cumulative decay cum, exp(cum) and w = dt *
exp(cum_last - cum).  None is (., S, 1): such a column is tiled (8, 128) in
HBM, 128 times its size.  The kernel turns the rows it needs as columns
(dt, cum, w) with one 2-D transpose each per step.

y comes out transposed, (B*H/hb, hb*dh, S) = (B, H*dh, S) with positions on
the lanes: every store is a whole tile, and the gated norm that follows
wants the sequence minor, so XLA takes the caller's (B, S, H, dh) as a
layout of this array without a copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of VMEM one grid step may hold: half the 16 MiB a Mosaic kernel may
# use by default on v5e, the rest left to the compiler's own temporaries
VMEM_BUDGET = 8 * 1024 * 1024


def _step_vmem_bytes(hb: int, q: int, dh: int, n: int) -> int:
    """VMEM a step of hb heads holds, f32 and lanes padded to 128:
    double-buffered x, B, C, four vector and y blocks, the state scratch and
    one head's (Q, Q) temporaries."""
    lanes = lambda k: -(-k // 128) * 128  # noqa: E731
    rows = -(-hb // 8) * 8
    blocks = hb * q * lanes(dh) + 2 * q * lanes(n) + 4 * rows * lanes(q) + hb * dh * lanes(q)
    return 4 * (2 * blocks + hb * dh * lanes(n) + 4 * q * lanes(q))


def ssd_blocking(b: int, s: int, h: int, g: int, dh: int, n: int, q: int
                 ) -> tuple[int, tuple[int, int]]:
    """(heads per step hb, grid) of the SSD kernel for x (b, s, h, dh), g
    B/C groups of state n, chunks of q: hb is the largest divisor of the
    heads per group whose step fits ``VMEM_BUDGET`` (at least 1)."""
    hpg = h // g
    hb = max(d for d in range(1, hpg + 1)
             if hpg % d == 0 and (d == 1 or _step_vmem_bytes(d, q, dh, n) <= VMEM_BUDGET))
    return hb, (b * h // hb, s // q)


def _ssd_kernel(xs_ref, bm_ref, cm_ref, dt_ref, cum_ref, ecum_ref, w_ref, y_ref,
                state_scr, *, hb: int, dh: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    bm = bm_ref[0].astype(jnp.float32)  # (Q, N)
    cm_t = cm_ref[0].astype(jnp.float32).T  # (N, Q)
    q = bm.shape[0]
    cum = cum_ref[0]  # (hb, Q) rows: positions on the lanes
    ecum = ecum_ref[0]  # (hb, Q)
    dt_c, cum_c, w_c = dt_ref[0].T, cum.T, w_ref[0].T  # (Q, hb) columns
    last = jax.lax.broadcasted_iota(jnp.int32, (hb, q), 1) == q - 1
    decay = jnp.sum(jnp.where(last, ecum, 0.0), axis=1, keepdims=True)  # (hb, 1)

    # shared by the block's heads: B C^T (source s by target t) and the mask
    gbc_t = jnp.dot(bm, cm_t, preferred_element_type=jnp.float32)  # (Q, Q)
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) <= jax.lax.broadcasted_iota(
        jnp.int32, (q, q), 1
    )
    # the carried states' readout for all hb heads at once: (hb*dh, Q)
    y_state = jnp.dot(state_scr[...], cm_t, preferred_element_type=jnp.float32)
    for j in range(hb):
        heads = slice(j * dh, (j + 1) * dh)
        xs_t = xs_ref[j].astype(jnp.float32).T  # (dh, Q)
        # intra-chunk: masked decay-weighted attention over the chunk, y^T
        lmat_t = jnp.where(causal, jnp.exp(cum[j:j + 1, :] - cum_c[:, j:j + 1]), 0.0)
        scores_t = gbc_t * lmat_t * dt_c[:, j:j + 1]
        y = jnp.dot(xs_t, scores_t, preferred_element_type=jnp.float32)  # (dh, Q)
        # inter-chunk: the carried state's readout, decayed to each position
        y += y_state[heads] * ecum[j:j + 1, :]
        y_ref[0, heads, :] = y.astype(y_ref.dtype)
        # state update: decay by the chunk's total, add the chunk's inputs
        contrib = jnp.dot(xs_t, bm * w_c[:, j:j + 1],
                          preferred_element_type=jnp.float32)  # (dh, N)
        state_scr[heads, :] = state_scr[heads, :] * decay[j:j + 1] + contrib


def ssd_chunked_tpu(xs, bm, cm, dt, a, *, chunk: int = 128, interpret: bool = False):
    """xs (B,S,H,dh), bm/cm (B,S,N) or (B,S,G,N), dt (B,S,H), a (H,) ->
    y (B,S,H,dh) f32."""
    b, s, h, dh = xs.shape
    n = bm.shape[-1]
    g = bm.shape[2] if bm.ndim == 4 else 1
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} must divide chunk {q}")
    hb, grid = ssd_blocking(b, s, h, g, dh, n, q)
    nb = h // hb  # head blocks per sequence
    per_group = (h // g) // hb  # head blocks per B/C group

    def groups_first(t):  # (B, S, G, N) -> (B*G, S, N); one group: (B, S, N)
        return jnp.moveaxis(t, 2, 1).reshape(b * g, s, n) if t.ndim == 4 else t

    bm, cm = groups_first(bm), groups_first(cm)
    nc = s // q
    # per-position vectors, heads first and positions on the lanes
    dt_h = jnp.swapaxes(dt, 1, 2).astype(jnp.float32).reshape(b, h, nc, q)
    cum = jnp.cumsum(dt_h * a[:, None, None], axis=-1)
    w = dt_h * jnp.exp(cum[..., q - 1:] - cum)
    rows = [t.reshape(b * nb, hb, s) for t in (dt_h, cum, jnp.exp(cum), w)]
    xs_h = jnp.moveaxis(xs, 2, 1).reshape(b * h, s, dh)  # (BH, S, dh)

    row = pl.BlockSpec((1, hb, q), lambda i, ci: (i, 0, ci))
    proj = pl.BlockSpec((1, q, n), lambda i, ci: (i // per_group, ci, 0))
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, hb=hb, dh=dh),
        grid=grid,
        in_specs=[
            pl.BlockSpec((hb, q, dh), lambda i, ci: (i, ci, 0)),
            proj, proj, row, row, row, row,
        ],
        out_specs=pl.BlockSpec((1, hb * dh, q), lambda i, ci: (i, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b * nb, hb * dh, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hb * dh, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xs_h, bm, cm, *rows)
    # (B*H/hb, hb*dh, S) is (B, H*dh, S): positions on the lanes
    return jnp.swapaxes(y.reshape(b, h * dh, s), 1, 2).reshape(b, s, h, dh)
