#!/usr/bin/env python3
"""What the comparison of a hybrid expert cell sees: the control runs of
``bench/control.py``, in which the reference with one fault in one block kind
also answers the sampled prompts in the program's place.

    python3 bench/faults.py --workload <cell> --seeds 11,12 [--seconds 10]

The cell's reference must have the hybrid mixers (``ref.MIXERS`` with ``M``,
``E`` and ``*``, as ``nemotron-3-nano-30b-a3b.ep8.ref.py``).  Each fault is a
change of the reference alone, made while it is traced:

- ``mixers_fp8``: every mixer's projections in float8 (``ref.dot_fp8``), the
  head in float32;
- ``mamba_one_group``: every Mamba head reads group 0's B and C;
- ``moe_held_dropped``: the held experts' part left out, the shared expert
  kept;
- ``attn_one_kv_head``: every query head reads KV head 0.

Each fault's answers are judged by ``harness.judge`` against the float32
reference under the cell's limit and printed to standard error as
``fault <name> rel_err_max <v> limit <l> correct <c>`` (the run's failed
requests are not counted in it); a limit that sees every block kind makes
each one false.  The result lines and the program's checks are
``bench/control.py``'s.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _tile_group0(w, start: int, g: int, n: int):
    """w with columns [start, start + g n) replaced by g copies of group 0's."""
    import jax.numpy as jnp

    return w.at[..., start:start + g * n].set(jnp.tile(w[..., start:start + n], g))


def _one_group(ref, cfg, lw):
    z = ref.dims(cfg)
    d_in, gn = z["d_in"], z["g"] * z["n"]
    lw = dict(lw)
    for start in (d_in, d_in + gn):  # B, then C, inside the conv's channels
        lw["in_proj"] = _tile_group0(lw["in_proj"], d_in + start, z["g"], z["n"])
        lw["conv_w"] = _tile_group0(lw["conv_w"], start, z["g"], z["n"])
        lw["conv_b"] = _tile_group0(lw["conv_b"], start, z["g"], z["n"])
    return lw


def _one_kv_head(ref, cfg, lw):
    import jax.numpy as jnp

    return dict(lw, wk=jnp.broadcast_to(lw["wk"][:, :1], lw["wk"].shape),
                wv=jnp.broadcast_to(lw["wv"][:, :1], lw["wv"].shape))


def _held_dropped(ref, cfg, lw):
    import jax.numpy as jnp

    return dict(lw, w_down=jnp.zeros_like(lw["w_down"]))


# name -> (the block kind it changes, or None for every kind; the weights'
# change; whether the mixer's products are float8)
FAULTS = {
    "mixers_fp8": (None, None, True),
    "mamba_one_group": ("M", _one_group, False),
    "moe_held_dropped": ("E", _held_dropped, False),
    "attn_one_kv_head": ("*", _one_kv_head, False),
}


def fault_dots(ref) -> dict:
    """Install the faults in ``ref`` (once) and return, per fault, the
    ``dot`` that selects it in ``ref.forward``: the reference's own float32
    product, a distinct object for each fault."""
    import functools

    if not hasattr(ref, "_fault_dots"):
        plain = dict(ref.MIXERS)
        dots = {name: functools.partial(ref.dot_f32) for name in FAULTS}
        by_dot = {id(dot): FAULTS[name] for name, dot in dots.items()}

        def mixer(kind):
            def run(cfg, lw, h, dot):
                fault = by_dot.get(id(dot))
                if fault is None:
                    return plain[kind](cfg, lw, h, dot)
                only, change, fp8 = fault
                if only not in (None, kind):
                    return plain[kind](cfg, lw, h, ref.dot_f32)
                if change is not None:
                    lw = change(ref, cfg, lw)
                return plain[kind](cfg, lw, h, ref.dot_fp8 if fp8 else ref.dot_f32)
            return run

        ref.MIXERS.update({kind: mixer(kind) for kind in plain})
        ref._fault_dots = dots
    return ref._fault_dots


def fault_errors(ref, cfg: dict, weights, tokens, block: int, names=tuple(FAULTS)) -> dict:
    """Relative L2 error of the answers to ``tokens`` (P, L) of each fault
    in ``names`` against the float32 reference, ``block`` prompts at a time."""
    from bench.harness import _rel_l2

    dots = {name: dot for name, dot in fault_dots(ref).items() if name in names}
    errs = {name: [] for name in dots}
    for i in range(0, tokens.shape[0], block):
        part = tokens[i:i + block]
        want = ref.forward(cfg, weights, part)
        for name, dot in dots.items():
            got = ref.forward(cfg, weights, part, dot)
            errs[name] += [_rel_l2(got[j], want[j]) for j in range(part.shape[0])]
    return errs


def main(argv=None) -> int:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax.numpy as jnp

    from bench import control, harness

    compare = harness.compare

    def compare_and_fault(ref, cfg, weights, ids, kept, block, control=False):
        out = compare(ref, cfg, weights, ids, kept, block, control)
        tokens = ids[jnp.asarray([r.prompt for r in kept])]
        for name, errs in fault_errors(ref, cfg, weights, tokens, block).items():
            correct, checks = harness.judge(errs, 0, cfg["check_limit"])
            worst = checks["rel_err_max"]
            print(f"fault {name} rel_err_max {worst['value']} limit {worst['limit']} "
                  f"correct {correct}", file=sys.stderr, flush=True)
        return out

    harness.compare = compare_and_fault
    return control.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
