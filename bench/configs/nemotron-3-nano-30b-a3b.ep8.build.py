"""Nemotron-3-Nano-30B-A3B's share of one chip under 8-way expert
parallelism, as a SEIFER user brings it: a ``LayerGraph`` and an
``executor_for_version`` composed from the program's own blocks.

Layer 0 is the embedding, layers 1..52 the blocks in the published pattern,
the last layer the final norm and the untied head at the last
``answer_positions`` positions.
Each block is ``models.layers.rmsnorm`` -> one mixer -> the residual add:

- ``M``: ``models.ssm.mamba_forward`` (grouped B/C, d_inner = heads x head
  size, grouped gated norm) around ``kernels.ssm_scan.ssd_chunked``;
- ``*``: ``models.layers.self_attention`` around
  ``kernels.flash_attention.flash_attention`` (16 query heads to a KV head,
  no positional encoding);
- ``E``: ``models.moe.held_expert_moe``, experts 0-15 of 128 through
  ``kernels.moe_gmm.gmm``, plus the shared expert.

Every kernel is called with the deployment's execution knob.  Each
``Layer`` carries its own kind's bytes and FLOPs, so the planner cuts a
chain of unequal layers.  ``runtime.pipeline.make_layer_executor`` turns
the layers into the executor the control plane deploys.  ``block`` is the
one block function, which ``bench/routes.py`` calls with the weights as
arguments.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core.graph import Layer, LayerGraph
from repro.kernels.flash_attention import flash_attention
from repro.models import ssm
from repro.models.layers import rmsnorm, self_attention
from repro.models.moe import held_expert_moe
from repro.runtime.pipeline import make_layer_executor


def ssm_config(cfg: dict) -> SimpleNamespace:
    """The sizes ``models/ssm.py`` reads, from the configuration's keys."""
    if cfg["mamba_head_dim"] != ssm.HEAD_DIM:
        raise ValueError(f"models/ssm.py serves head size {ssm.HEAD_DIM}")
    return SimpleNamespace(
        d_model=cfg["hidden_size"], ssm_heads=cfg["mamba_num_heads"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        ssm_conv_width=cfg["conv_kernel"], ssm_norm_eps=cfg["layer_norm_epsilon"])


def block(cfg: dict, kind: str, p: dict, x, *, seq: int, use_pallas: bool,
          interpret: bool):
    """One residual block of ``kind`` (M, E or *) with weights ``p``."""
    knob = dict(use_pallas=use_pallas, interpret=interpret)
    h = rmsnorm(x, p["norm"], eps=cfg["layer_norm_epsilon"])
    if kind == "M":
        y = ssm.mamba_forward(ssm_config(cfg), p, h, chunk=min(cfg["chunk_size"], seq), **knob)
    elif kind == "E":
        y = held_expert_moe(p, h, top_k=cfg["num_experts_per_tok"],
                            scaling=cfg["routed_scaling_factor"],
                            first_expert=cfg["first_held_expert"], **knob)
    else:
        attend = functools.partial(flash_attention, causal=True,
                                   block=cfg["assumed"]["flash_block"], **knob)
        y = self_attention(p, h, attend)
    return x + y


def build(cfg: dict, weights: dict, ref, *, seq: int, use_pallas: bool,
          interpret: bool):
    """-> (graph, executor_for_version).  ``ref`` is the configuration's
    reference module, for the pattern and the FLOP count."""
    z = ref.dims(cfg)
    kinds = ref.kinds(cfg)
    if ssm.ssm_dims(ssm_config(cfg)) != (z["d_in"], z["h"], z["n"]):
        raise ValueError(f"models/ssm.py sizes {ssm.ssm_dims(ssm_config(cfg))} "
                         f"differ from the config")
    run = functools.partial(block, cfg, seq=seq, use_pallas=use_pallas, interpret=interpret)

    def embed(tokens):
        return weights["embed"][tokens]

    def head(x):
        h = rmsnorm(x[:, -cfg["answer_positions"]:], weights["norm_f"],
                    eps=cfg["layer_norm_epsilon"])
        return jnp.einsum("bld,vd->blv", h, weights["lm_head"],
                          preferred_element_type=jnp.float32)

    def nbytes(tree):
        return sum(int(a.size) * a.dtype.itemsize for a in jax.tree.leaves(tree))

    fns = ([embed] + [functools.partial(run, kind, p) for kind, p in zip(kinds, weights["layers"])]
           + [head])
    act = seq * z["d"] * 2  # bf16 residual stream between stages
    flops = ref.flops_by_kind(cfg, seq)
    layers = ([Layer("embed", nbytes(weights["embed"]), act, 0)]
              + [Layer(f"{kind}{i}", nbytes(p), act, int(flops[kind]))
                 for i, (kind, p) in enumerate(zip(kinds, weights["layers"]))]
              + [Layer("head", nbytes(weights["lm_head"]) + nbytes(weights["norm_f"]),
                       cfg["answer_positions"] * z["vocab"] * 4, int(flops["head"]))])
    graph = LayerGraph(cfg["name"], tuple(layers), in_bytes=seq * 4)
    executor = make_layer_executor(fns)
    return graph, lambda version: executor
