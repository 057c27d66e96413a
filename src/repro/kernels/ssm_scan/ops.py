"""Jit'd wrapper for the chunked SSD scan (Pallas on TPU, jnp ref on host)."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.ssm_scan.kernel import ssd_chunked_tpu
from repro.kernels.ssm_scan.ref import ssd_ref


@partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd_chunked(xs, bm, cm, dt, a, *, chunk: int = 128, use_pallas: bool = False,
                interpret: bool = False):
    """Chunked selective-state scan.  ``bm``/``cm`` are (B,S,N) for one B/C
    group or (B,S,G,N) for G groups.  Returns y (B,S,H,dh) f32."""
    if use_pallas:
        return ssd_chunked_tpu(xs, bm, cm, dt, a, chunk=chunk, interpret=interpret)
    y, _ = ssd_ref(xs, bm, cm, dt, a, chunk=chunk)
    return y
