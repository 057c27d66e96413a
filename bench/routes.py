#!/usr/bin/env python3
"""The routing counter of a cell whose configuration routes tokens to
experts, run by hand on the chip, outside any timed run.

    python3 bench/routes.py --workload <cell> --seed <n> [--prompts 8]

It makes the cell's weights and prompt pool from the seed as a run does and
takes the pool's first ``--prompts`` prompts.  It runs the program's blocks
(the configuration's ``build.block``, jitted per kind with the weights as
arguments, on the compiled kernels) and the reference's float32 blocks side
by side, ``ref_block`` prompts at a time.  At each routed-expert block it
routes the program's normed bf16 residual with the program's router
(``models.moe.sigmoid_route``) and the reference's float32 residual with the
reference's (``ref.route``), and counts:

- ``rows``: for each held expert, the picks the program sends it, summed
  over the routed-expert blocks;
- ``held_share``: those rows over all picks (balanced routing gives held /
  experts);
- ``mismatch``: the share of the program's (token, pick) pairs whose expert
  is not among the reference's picks for that token.

Prints one JSON line.  Exits non-zero when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def count_routes(cfg: dict, weights: dict, ref, build, tokens, *, use_pallas: bool = True) -> dict:
    """The counter over ``tokens`` (P, L), ``ref_block`` prompts at a time;
    ``use_pallas=False`` runs the program's blocks on their jnp paths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.layers import rmsnorm
    from repro.models.moe import sigmoid_route

    seq, eps = tokens.shape[1], cfg["layer_norm_epsilon"]
    kinds = ref.kinds(cfg)
    program = {k: jax.jit(functools.partial(build.block, cfg, k, seq=seq, use_pallas=use_pallas,
                                            interpret=False)) for k in set(kinds)}
    reference, _ = ref._jitted(ref._cfg_key(cfg), ref.dot_f32)

    @jax.jit
    def program_route(p, x):
        h = rmsnorm(x, p["norm"], eps=eps)
        return sigmoid_route(h.reshape(-1, h.shape[-1]), p["router"], p["bias"],
                             top_k=cfg["num_experts_per_tok"],
                             scaling=cfg["routed_scaling_factor"])[0]

    @jax.jit
    def reference_route(p, x):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        experts = ref.route(cfg, p, ref.rmsnorm(x, p["norm"], eps))[0]
        return experts.reshape(-1, cfg["num_experts_per_tok"])

    first, held = cfg["first_held_expert"], cfg["n_routed_experts"]
    rows = np.zeros(held, np.int64)
    picks = differ = 0
    block = int(cfg.get("ref_block", 1))
    for i in range(0, tokens.shape[0], block):
        ids = tokens[i:i + block]
        x = weights["embed"][ids]
        xr = x.astype(jnp.float32)
        for kind, p in zip(kinds, weights["layers"]):
            if kind == "E":
                mine = np.asarray(program_route(p, x))
                theirs = np.asarray(reference_route(p, xr))
                local = mine - first
                rows += np.bincount(local[(local >= 0) & (local < held)], minlength=held)
                picks += mine.size
                differ += int(sum(len(set(a) - set(b)) for a, b in zip(mine, theirs)))
            x, xr = program[kind](p, x), reference[kind](p, xr)
    return {"prompts": int(tokens.shape[0]), "picks": picks, "rows": rows.tolist(),
            "held_share": float(rows.sum() / picks), "balanced_share": held / cfg["n_router_experts"],
            "mismatch": differ / picks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompts", type=int, default=8)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import traffic
    from bench.harness import NoChip, check_devices, configure_cache, make_prompts
    from bench.manifest import Manifest

    manifest = Manifest.load(ROOT / "BENCHMARK.json")
    cell = manifest.cell(args.workload)
    cfg, mix = manifest.config_json(cell), manifest.traffic(cell)
    try:
        check_devices(cell.chips)
    except NoChip as e:
        print(f"bench/routes.py: {e}", file=sys.stderr)
        return 3
    configure_cache()
    ref, build = manifest.reference(cell), manifest.builder(cell)
    words = traffic.seed_words(args.seed, 4)
    weights = ref.init_weights(cfg, words[:2])
    ids, _ = make_prompts(words[2:], int(mix["prompt_pool"]), int(mix["prompt_len"]),
                          int(cfg["vocab_size"]))
    out = count_routes(cfg, weights, ref, build, ids[:args.prompts])
    print(json.dumps(dict(out, workload=args.workload, seed=args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
