#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for (``BENCHMARK.json``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the same window runs under the
profiler and the result carries its per-layer metrics, the device's busy
and window seconds, and a breakdown.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell needs.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        import repro  # noqa: F401
        from bench.harness import NoChip, dumps, run_cell
        from bench.manifest import Manifest, ManifestError
    except ImportError as e:
        print(f"bench/run.py: cannot import the program from {ROOT / 'src'}: {e}",
              file=sys.stderr)
        return 2
    try:
        manifest = Manifest.load(ROOT / "BENCHMARK.json")
        result = run_cell(manifest, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS,
                          out_dir=ROOT / "results")
    except NoChip as e:
        print(f"bench/run.py: {e}; this benchmark runs only on the chip", file=sys.stderr)
        return 3
    except ManifestError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
