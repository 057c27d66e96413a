#!/usr/bin/env python3
"""The control of a cell's comparison: whole runs of the cell, judged on the
reference computed one precision step below the configuration's bf16.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 51]

Each seed is a whole run of the cell (``harness.run_cell`` with
``control=True``): the same set-up, window and seeded sample of answered
requests as a benchmark run.  The control's answers to the sampled prompts
(every projection in float8_e4m3 with a per-tensor scale, ``ref.dot_fp8``)
then take the program's place, and the harness judges them against the
float32 reference under the cell's own limit and rule.  Each run's result
line is printed, ``correct`` in it; a sound limit makes every one false.
The program's own checks of the same run go to standard error as
``program check ...``.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per run (default: BENCHMARK.json's run_seconds)")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import NoChip, dumps, run_cell
    from bench.manifest import Manifest

    manifest = Manifest.load(ROOT / "BENCHMARK.json")
    seconds = args.seconds or float(manifest.data["run_seconds"])
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            result = run_cell(manifest, args.workload, seed, seconds, False,
                              control=True, out_dir=ROOT / "results")
        except NoChip as e:
            print(f"bench/control.py: {e}", file=sys.stderr)
            return 3
        print(dumps(dict(result, workload=args.workload, seed=seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
