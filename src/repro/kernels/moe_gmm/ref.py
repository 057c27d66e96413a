"""Pure-jnp oracle for the grouped matmul over held experts.

``lhs`` (m, k) holds rows sorted by expert; ``group_sizes`` (experts,)
counts each expert's rows; ``rhs`` (held, k, n) holds the weights of experts
``group_offset .. group_offset + held - 1``.  Row r of the output is
``lhs[r] @ rhs[e - group_offset]`` for the expert e whose rows hold r when e
is held, and zero otherwise.  One masked full product per held expert.
"""

from __future__ import annotations

import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes, *, group_offset: int = 0, out_dtype=jnp.float32):
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for j in range(rhs.shape[0]):
        e = group_offset + j
        mine = (row >= starts[e]) & (row < ends[e])
        part = jnp.dot(lhs, rhs[j], preferred_element_type=jnp.float32)
        out = out + jnp.where(mine[:, None], part, 0.0)
    return out.astype(out_dtype)
