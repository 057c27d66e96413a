"""A tiny copy of the benchmark for the CPU tests.

``tiny_bench(root)`` copies ``bench/`` under ``root`` and adds, by files and
manifest entries alone, two tiny-width configurations built by the real
configurations' builder and reference files, two small traffic mixes and a
manifest that names them: the way a later change adds a cell.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_CONFIGS = {
    "tiny-mamba": ("mamba2-2.7b", {
        "d_model": 64, "n_layer": 2, "vocab_size": 250, "d_state": 16, "chunk_size": 8,
        "ref_block": 2}, {"kernel_chunk": 8}),
    "tiny-nemo": ("mistral-nemo-12b.l16", {
        "dim": 64, "n_layers": 6, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "hidden_dim": 128, "vocab_size": 256, "ref_block": 2}, {"flash_block": 64}),
}

TINY_CELLS = {  # cell -> (config, traffic, prompt_len)
    "tiny-mamba.closed": ("tiny-mamba", "tiny-closed", 16),
    "tiny-nemo.closed": ("tiny-nemo", "tiny-closed128", 128),
}


def tiny_bench(root: Path, limit: float = 0.05) -> Path:
    """Build the tiny tree under ``root``; returns its BENCHMARK.json.

    The tiny sizes' own limit: on the CPU, seeds 11-13 and 2**40 + 7, the
    program read at most 0.025 (mamba) and 0.013 (nemo), the float8 control
    at least 0.111 and 0.067."""
    dst = root / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, (base, sizes, assumed) in TINY_CONFIGS.items():
        cfg = json.loads((BENCH / f"configs/{base}.json").read_text())
        cfg.update(sizes, name=name, check_limit=limit)
        cfg["assumed"].update(assumed)
        (dst / f"configs/{name}.json").write_text(json.dumps(cfg))
        for ext in ("build.py", "ref.py"):
            shutil.copy(BENCH / f"configs/{base}.{ext}", dst / f"configs/{name}.{ext}")
    for cell, (_, mix_name, seq) in TINY_CELLS.items():
        mix = json.loads((BENCH / "traffic/sat-int8.json").read_text())
        mix.update(prompt_len=seq, prompt_pool=16, clients=4, check={"sample": 3})
        mix["deployment"].update(max_batch=2, microbatch=2)
        (dst / f"traffic/{mix_name}.json").write_text(json.dumps(mix))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    man["configs"] = [{"name": n, "source": "tiny test size", "file": f"bench/configs/{n}.json",
                       "reduced": [], "why": "CPU test"} for n in TINY_CONFIGS]
    man["workloads"] = [{"name": c, "config": cfg, "traffic": mix, "chips": 1, "why": "CPU test"}
                        for c, (cfg, mix, _) in TINY_CELLS.items()]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(TINY_CELLS)
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    return path
