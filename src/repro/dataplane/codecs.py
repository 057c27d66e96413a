"""The built-in inter-stage transfer codecs.

SEIFER/DEFER compress inter-partition activations on the wire (ZFP/LZ4 in
the papers); these are the TPU-native analogues, each registered by name so
``DeploymentSpec(codec=...)`` can put any of them on a link:

  =============  ========  ============  =======================================
  codec          ~ratio    error bound   mechanism
  =============  ========  ============  =======================================
  identity       1.000     0 (lossless)  raw f32 bytes (the historical wire)
  fp16           0.500     2^-11         float16 truncation
  int8           0.254     1/254         blockwise int8 (``kernels/quantize``:
                                         the Pallas kernel when the knob says
                                         so, else its jnp ref or numpy twin)
  topk-sparse    0.500     1 (unbounded) top-25% magnitudes as (index, value)
  =============  ========  ============  =======================================

Ratios are for f32 activations.  Transforms accept jax *or* numpy arrays and
return the same kind -- the engine feeds jax microbatches, unit tests feed
numpy.  An int8 codec configured for the Pallas kernel takes jax arrays only.
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np

from repro.dataplane.base import Codec, _itemsize
from repro.dataplane.registry import register_codec

from repro.kernels.quantize import INT8_MAX_REL_ERROR, dequantize_int8, quantize_int8


def _is_jax(x: Any) -> bool:
    return type(x).__module__.startswith(("jax", "jaxlib"))


@register_codec("identity", default=True)
class IdentityCodec(Codec):
    """Raw activations on the wire; the no-compression baseline."""

    def encode(self, x):
        return x

    def decode(self, payload):
        return payload

    def transcode(self, x):
        return x

    def wire_ratio(self, elem_bytes: float = 4.0) -> float:
        return 1.0


@register_codec("fp16")
class Fp16Codec(Codec):
    """float16 truncation: half the bytes at ~2^-11 relative error.

    The reported bound holds for activations within float16's finite range
    (|x| <= 65504, every normalized network in practice); larger values are
    clamped to the range edge on encode -- a graceful accuracy loss there,
    never an inf/NaN poisoning the downstream stages.
    """

    F16_MAX = 65504.0
    error_bound = 2.0 ** -11
    encode_flops_per_byte = 0.25  # one convert per f32 element
    decode_flops_per_byte = 0.25

    def encode(self, x):
        if _is_jax(x):
            import jax.numpy as jnp

            clamped = jnp.clip(x, -self.F16_MAX, self.F16_MAX)
            return clamped.astype(jnp.float16), x.dtype
        x = np.asarray(x)
        clamped = np.clip(x, -self.F16_MAX, self.F16_MAX)
        return clamped.astype(np.float16), x.dtype

    def decode(self, payload):
        y, dtype = payload
        return y.astype(dtype)

    def wire_ratio(self, elem_bytes: float = 4.0) -> float:
        return 2.0 / elem_bytes


@register_codec("int8")
class Int8Codec(Codec):
    """Blockwise symmetric int8 (``kernels/quantize``): 1 byte per element
    plus one f32 scale per ``block``; error <= scale/2 per element."""

    block = 256
    error_bound = INT8_MAX_REL_ERROR
    encode_flops_per_byte = 1.5  # abs/max-reduce/div/round/clip per element
    decode_flops_per_byte = 0.5  # mul + cast per element
    # execution knob (see repro.core.execution): deployments flip these via
    # ``configured()`` so the registry singleton keeps the ref defaults
    use_pallas = False
    interpret = False

    def encode(self, x):
        if _is_jax(x):
            q, s = quantize_int8(x, block=self.block, use_pallas=self.use_pallas,
                                 interpret=self.interpret)
            return "jax", q, s, x.dtype
        if self.use_pallas:
            # the numpy twin would run on the host, out of the kernel's sight
            raise TypeError(f"int8 codec on the Pallas path needs a jax array, "
                            f"got {type(x).__name__}")
        x = np.asarray(x)
        q, s = _np_quantize(x, self.block)
        return "np", q, s, x.dtype

    def decode(self, payload):
        kind, q, s, dtype = payload
        if kind == "jax":
            return dequantize_int8(q, s, dtype=dtype, block=self.block,
                                   use_pallas=self.use_pallas,
                                   interpret=self.interpret)
        return _np_dequantize(q, s, self.block).astype(dtype)

    def wire_ratio(self, elem_bytes: float = 4.0) -> float:
        return (1.0 + 4.0 / self.block) / elem_bytes

    def compressed_bytes(self, shape, dtype=None) -> int:
        *lead, d = shape
        n_blocks = math.prod(lead) * -(-d // self.block)
        return int(math.prod(shape)) + 4 * int(n_blocks)


@register_codec("topk-sparse")
class TopKSparseCodec(Codec):
    """Magnitude top-k sparsification: the largest ``keep_frac`` of the
    elements as (int32 index, value) pairs, zeros elsewhere.  The reported
    error bound is 1.0 -- a dropped element can be as large as the kept
    threshold -- so ``auto`` only picks it when the tolerance says the
    caller genuinely does not care."""

    keep_frac = 0.25
    error_bound = 1.0
    encode_flops_per_byte = 4.0  # selection dominates
    decode_flops_per_byte = 0.25  # scatter into zeros

    def _k(self, n: int) -> int:
        return max(1, int(math.ceil(self.keep_frac * n)))

    def encode(self, x):
        if _is_jax(x):
            import jax
            import jax.numpy as jnp

            flat = x.reshape(-1)
            _, idx = jax.lax.top_k(jnp.abs(flat), self._k(flat.shape[0]))
            return "jax", x.shape, x.dtype, idx, flat[idx]
        x = np.asarray(x)
        flat = x.reshape(-1)
        k = self._k(flat.size)
        idx = np.argpartition(np.abs(flat), -k)[-k:]
        return "np", x.shape, x.dtype, idx, flat[idx]

    def decode(self, payload):
        kind, shape, dtype, idx, vals = payload
        if kind == "jax":
            import jax.numpy as jnp

            n = math.prod(shape)
            flat = jnp.zeros((n,), dtype).at[idx].set(vals)
            return flat.reshape(shape)
        flat = np.zeros((math.prod(shape),), dtype)
        flat[idx] = vals
        return flat.reshape(shape)

    def wire_ratio(self, elem_bytes: float = 4.0) -> float:
        return self.keep_frac * (elem_bytes + 4.0) / elem_bytes

    def compressed_bytes(self, shape, dtype=None) -> int:
        k = self._k(int(math.prod(shape)))
        return int(k * (_itemsize(dtype) + 4.0))


# ---------------------------------------------------------------------------
# numpy fallback for the int8 transform.  Mirrors kernels/quantize/ref.py
# (which must stay jnp so it lowers under jit and cannot be imported without
# jax); tests/test_dataplane.py pins the two byte-for-byte so they cannot
# drift apart silently.
# ---------------------------------------------------------------------------

def _np_quantize(x: np.ndarray, block: int) -> tuple[np.ndarray, np.ndarray]:
    *lead, d = x.shape
    nb = -(-d // block)
    pad = nb * block - d
    xf = np.asarray(x, np.float32)
    if pad:
        xf = np.pad(xf, [(0, 0)] * len(lead) + [(0, pad)])
    xb = xf.reshape(*lead, nb, block)
    scale = np.max(np.abs(xb), axis=-1) / 127.0
    safe = np.maximum(scale, 1e-12)
    q = np.clip(np.round(xb / safe[..., None]), -127, 127).astype(np.int8)
    return q.reshape(*lead, nb * block)[..., :d], scale


def _np_dequantize(q: np.ndarray, scale: np.ndarray, block: int) -> np.ndarray:
    *lead, d = q.shape
    nb = scale.shape[-1]
    pad = nb * block - d
    qf = np.asarray(q, np.float32)
    if pad:
        qf = np.pad(qf, [(0, 0)] * len(lead) + [(0, pad)])
    xb = qf.reshape(*lead, nb, block) * scale[..., None]
    return xb.reshape(*lead, nb * block)[..., :d]
