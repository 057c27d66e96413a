"""The one traffic generator: every mix is a data file that this reads.

A mix (``bench/traffic/<name>.json``) gives:

    loop            "closed": ``clients`` callers, each sends its next request
                    when its last one is answered
    prompt_len      tokens per prompt, the same for every request
    prompt_pool     distinct prompts drawn from the seed; request i sends
                    prompt i mod pool
    deployment      codec, max_batch, microbatch, queue_depth, nodes,
                    capacity_frac (of the model's bytes per node), cluster_seed
    check           sample: how many answers are compared with the reference,
                    drawn from the seed over every answer of the run
"""

from __future__ import annotations

import numpy as np

KINDS = ("closed",)


def seed_words(seed: int, n: int = 2) -> list[int]:
    """``n`` 31-bit words mixed from a seed of any size (JAX keys keep only
    32 bits of a Python int, so large seeds are hashed, not truncated)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, dtype=np.uint32)
    return [int(w) & 0x7FFFFFFF for w in state]


def rng(seed: int, stream: str) -> np.random.Generator:
    """A host generator for one named stream of a run's seed."""
    tag = int.from_bytes(stream.encode(), "little") % (2 ** 63)
    return np.random.default_rng([int(seed) % (2 ** 63), tag])


def validate(mix: dict) -> dict:
    if mix.get("loop") not in KINDS:
        raise ValueError(f"traffic loop must be one of {KINDS}, got {mix.get('loop')!r}")
    if int(mix.get("clients", 0)) < 1:
        raise ValueError("a closed loop needs clients >= 1")
    if int(mix.get("prompt_len", 0)) < 1 or int(mix.get("prompt_pool", 0)) < 1:
        raise ValueError("prompt_len and prompt_pool must be >= 1")
    if int(mix.get("check", {}).get("sample", 0)) < 1:
        raise ValueError("check.sample must be >= 1")
    return mix


def batch_cap(mix: dict) -> int:
    """Largest batch the engine can form under this mix's deployment."""
    dep = mix["deployment"]
    return int(dep.get("max_batch") or dep.get("microbatch", 4))


def warm_batch_sizes(mix: dict) -> list[int]:
    """The batch sizes this mix makes the engine form.  Clients that fill
    whole batches only ever form full ones (a batch's callers come back
    together); other counts can form every size from 1 to the cap."""
    cap = batch_cap(mix)
    if int(mix["clients"]) % cap == 0:
        return [cap]
    return list(range(1, cap + 1))
