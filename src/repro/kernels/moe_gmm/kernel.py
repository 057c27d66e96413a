"""Pallas TPU grouped matmul over the experts a chip holds.

Wraps the megablox kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox.gmm``): rows of ``lhs`` come
sorted by expert, ``group_sizes`` counts them for every expert of the router,
and ``rhs`` holds the weights of the experts ``group_offset ..
group_offset + rhs.shape[0] - 1`` only.  The kernel's grid visits only the
m-tiles of those experts' rows; every other row of the output is zero.
It masks a ragged last k tile and clips a ragged last n tile, so widths such
as 1856 and 2688 need no padding.

Tiles: ``tm`` rows (the largest power of two up to 512 that divides m), and
k and n tiles of up to 1024.  VMEM at (512, 1024, 1024) in bf16: lhs 1 MiB
and rhs 2 MiB, each double-buffered, a 2 MiB f32 accumulator and a 1 MiB
output tile, double-buffered -- about 10 MiB.  A held expert's weights are
read once per m-tile of its rows, so the taller tile reads them fewer times.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

TILE_M, TILE_KN = 512, 1024


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for an (m, k) x (k, n) grouped product."""
    def kn(x):
        return min(TILE_KN, -(-x // 128) * 128)
    return math.gcd(m, TILE_M), kn(k), kn(n)


def gmm_tpu(lhs, rhs, group_sizes, *, group_offset: int = 0, out_dtype=jnp.float32,
            interpret: bool = False):
    """lhs (m, k), rhs (held, k, n), group_sizes (experts,) int32 -> (m, n)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    return megablox_gmm(
        lhs, rhs, group_sizes, preferred_element_type=out_dtype,
        tiling=tiling(m, k, n), group_offset=jnp.asarray(group_offset, jnp.int32),
        interpret=interpret)
