"""Mamba2-2.7B as a SEIFER user brings it: a ``LayerGraph`` and an
``executor_for_version`` composed from the program's own blocks.

Layer 0 is the embedding, layers 1..n_layer the residual blocks, the last
layer the final norm and the tied head at the last position.  Each block is
``models.layers.rmsnorm`` -> ``models.ssm`` projections, causal conv and
gated norm around ``kernels.ssm_scan.ssd_chunked`` (called with the
deployment's execution knob, as the zoo's models call it), with the residual
add of the published block.  ``runtime.pipeline.make_layer_executor`` turns
the layers into the executor the control plane deploys.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core.graph import Layer, LayerGraph
from repro.kernels.ssm_scan.ops import ssd_chunked
from repro.models import ssm
from repro.models.layers import rmsnorm
from repro.runtime.pipeline import make_layer_executor


def build(cfg: dict, weights: dict, ref, *, seq: int, use_pallas: bool,
          interpret: bool):
    """-> (graph, executor_for_version).  ``ref`` is the configuration's
    reference module, for the shapes and the FLOP count."""
    z = ref.dims(cfg)
    if z["p"] != ssm.HEAD_DIM or cfg["ngroups"] != 1:
        raise ValueError("models/ssm.py serves headdim 64 with one B/C group")
    mcfg = SimpleNamespace(d_model=z["d"], ssm_expand=cfg["expand"],
                           ssm_state=z["n"], ssm_conv_width=z["k"])
    if ssm.ssm_dims(mcfg) != (z["d_in"], z["h"], z["n"]):
        raise ValueError(f"models/ssm.py sizes {ssm.ssm_dims(mcfg)} differ from the config")
    eps = cfg["norm_epsilon"]
    chunk = min(cfg["assumed"]["kernel_chunk"], seq)

    def embed(tokens):
        return weights["embed"][tokens]

    def make_block(p):
        def block(x):
            bsz, s, _ = x.shape
            h = rmsnorm(x, p["norm"], eps=eps)
            zg, xbc, dt = ssm._split_proj(mcfg, p, h)
            xbc = jax.nn.silu(ssm._causal_conv(xbc, p["conv_w"], p["conv_b"]))
            xs = xbc[..., :z["d_in"]].reshape(bsz, s, z["h"], z["p"])
            bm = xbc[..., z["d_in"]:z["d_in"] + z["n"]].astype(jnp.float32)
            cm = xbc[..., z["d_in"] + z["n"]:].astype(jnp.float32)
            y = ssd_chunked(xs, bm, cm, dt, -jnp.exp(p["A_log"]), chunk=chunk,
                            use_pallas=use_pallas, interpret=interpret)
            y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
            return x + ssm._gate_out(mcfg, p, y.reshape(bsz, s, z["d_in"]), zg)
        return block

    def head(x):
        h = rmsnorm(x[:, -1], weights["norm_f"], eps=eps)
        return jnp.einsum("bd,vd->bv", h, weights["embed"],
                          preferred_element_type=jnp.float32)

    fns = [embed] + [make_block(p) for p in weights["layers"]] + [head]
    act = seq * z["d"] * 2  # bf16 residual stream between stages
    table = z["vocab"] * z["d"] * 2
    per_block = (ref.flops_per_request(cfg, seq) - 2 * z["d"] * z["vocab"]) / cfg["n_layer"]
    block_bytes = sum(int(a.size) * a.dtype.itemsize for a in weights["layers"][0].values())
    layers = ([Layer("embed", table, act, 0)]
              + [Layer(f"block{i}", block_bytes, act, int(per_block))
                 for i in range(cfg["n_layer"])]
              + [Layer("head", table, z["vocab"] * 4, 2 * z["d"] * z["vocab"])])
    graph = LayerGraph(cfg["name"], tuple(layers), in_bytes=seq * 4)
    executor = make_layer_executor(fns)
    return graph, lambda version: executor
