"""Grouped matmul over held experts: the Pallas kernel on the deployment's
execution knob, or the jnp reference."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.moe_gmm.kernel import gmm_tpu
from repro.kernels.moe_gmm.ref import gmm_ref


def gmm(lhs, rhs, group_sizes, *, group_offset: int = 0, out_dtype=jnp.float32,
        use_pallas: bool = False, interpret: bool = False):
    """Rows of ``lhs`` (m, k), sorted by expert and counted per expert by
    ``group_sizes`` (int32, one entry per expert of the router), times the
    weights ``rhs`` (held, k, n) of experts ``group_offset ..
    group_offset + held - 1``.  Returns (m, n) in ``out_dtype``, zero in the
    rows of experts not held."""
    if use_pallas:
        return gmm_tpu(lhs, rhs, group_sizes, group_offset=group_offset,
                       out_dtype=out_dtype, interpret=interpret)
    return gmm_ref(lhs, rhs, group_sizes, group_offset=group_offset, out_dtype=out_dtype)
