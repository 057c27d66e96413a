"""Pallas TPU kernels: blockwise int8 quantize / dequantize.

Tiling: rows of the flattened (N, d) input are processed ``row_tile`` at a
time; the trailing dim is reshaped to (d/block, block) inside the kernel so
the VPU reduces |x| over the lane dimension.  VMEM per step at defaults
(row_tile=256, d=8192, bf16): in 4 MiB + out 2 MiB + scales 128 KiB -- fits
comfortably with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref, *, block: int):
    x = x_ref[...].astype(jnp.float32)  # (rows, d)
    rows, d = x.shape
    xb = x.reshape(rows, d // block, block)
    scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / safe[..., None]), -127, 127)
    q_ref[...] = q.reshape(rows, d).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref, *, block: int):
    rows, d = q_ref.shape
    # widen before splitting the lanes into blocks: Mosaic cannot reshape an
    # int8 vector whose block is narrower than a lane tile
    qb = q_ref[...].astype(jnp.float32).reshape(rows, d // block, block)
    x = qb * s_ref[...][..., None]
    x_ref[...] = x.reshape(rows, d).astype(x_ref.dtype)


def _dqmm_kernel(q_ref, s_ref, w_ref, o_ref, *, block: int):
    rows, d = q_ref.shape
    qb = q_ref[...].astype(jnp.float32).reshape(rows, d // block, block)
    x = (qb * s_ref[...][..., None]).reshape(rows, d)
    o_ref[...] = jax.lax.dot_general(
        x, w_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def quantize_int8_tpu(
    x: jax.Array, block: int = 256, row_tile: int = 256, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """x (..., d) -> (int8 (..., d), f32 scales (..., ceil(d/block))).

    A ragged trailing dim is zero-padded to the next block boundary before
    the kernel (padding never raises a block's max-abs, so the scales match
    the ref's exactly) and sliced back after."""
    *lead, d = x.shape
    nb = -(-d // block)
    dp = nb * block
    n = 1
    for s in lead:
        n *= s
    x2 = x.reshape(n, d)
    if dp != d:
        x2 = jnp.pad(x2, ((0, 0), (0, dp - d)))
    rt = min(row_tile, n)
    if n % rt:
        rt = n
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=(n // rt,),
        in_specs=[pl.BlockSpec((rt, dp), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((rt, nb), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dp), jnp.int8),
            jax.ShapeDtypeStruct((n, nb), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    return q[:, :d].reshape(*lead, d), s.reshape(*lead, nb)


def dequantize_int8_tpu(
    q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16,
    row_tile: int = 256, interpret: bool = False, block: int | None = None,
) -> jax.Array:
    *lead, d = q.shape
    nb = scale.shape[-1]
    if block is None:
        if d % nb:
            raise ValueError(
                f"trailing dim {d} is ragged over {nb} scale blocks; "
                f"pass the block= used to quantize"
            )
        block = d // nb
    dp = nb * block
    n = 1
    for s in lead:
        n *= s
    q2 = q.reshape(n, d)
    if dp != d:
        q2 = jnp.pad(q2, ((0, 0), (0, dp - d)))
    s2 = scale.reshape(n, nb)
    rt = min(row_tile, n)
    if n % rt:
        rt = n
    x = pl.pallas_call(
        functools.partial(_dequant_kernel, block=block),
        grid=(n // rt,),
        in_specs=[
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((rt, nb), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rt, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dp), dtype),
        interpret=interpret,
    )(q2, s2)
    return x[:, :d].reshape(*lead, d)


def dequant_matmul_tpu(
    q: jax.Array, scale: jax.Array, w: jax.Array, dtype=None,
    row_tile: int = 256, interpret: bool = False, block: int | None = None,
) -> jax.Array:
    """Fused dequantize-into-matmul: ``dequant(q, scale) @ w`` per row tile.

    The int8 tile is widened and scaled in VMEM and fed straight to the MXU
    -- the dequantized activation never round-trips through HBM, which is
    the whole point of receiving a quantized boundary activation.  ``w``
    (d, dout) rides whole in VMEM; its rows are zero-padded alongside a
    ragged ``q`` trailing dim (padded q is zero, so the extra rows are
    inert either way)."""
    *lead, d = q.shape
    nb = scale.shape[-1]
    if block is None:
        if d % nb:
            raise ValueError(
                f"trailing dim {d} is ragged over {nb} scale blocks; "
                f"pass the block= used to quantize"
            )
        block = d // nb
    dp = nb * block
    dout = w.shape[-1]
    n = 1
    for s in lead:
        n *= s
    q2 = q.reshape(n, d)
    w2 = w
    if dp != d:
        q2 = jnp.pad(q2, ((0, 0), (0, dp - d)))
        w2 = jnp.pad(w, ((0, dp - d), (0, 0)))
    s2 = scale.reshape(n, nb)
    rt = min(row_tile, n)
    if n % rt:
        rt = n
    o = pl.pallas_call(
        functools.partial(_dqmm_kernel, block=block),
        grid=(n // rt,),
        in_specs=[
            pl.BlockSpec((rt, dp), lambda i: (i, 0)),
            pl.BlockSpec((rt, nb), lambda i: (i, 0)),
            pl.BlockSpec((dp, dout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rt, dout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dout), w.dtype if dtype is None else dtype),
        interpret=interpret,
    )(q2, s2, w2)
    return o.reshape(*lead, dout)
