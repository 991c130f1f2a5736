#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --seconds 15 [--kv-dtype fp8_e4m3]

One set-up; then for each seed, new weights from that seed in the same
engine and programs, a window of the seed's own traffic at the cell's
load, the drain, and the check with the reference computed in float8 put
in the program's place (``Options.control``): ``correct`` is then the
control's, and ``readings`` holds the program's gaps (its lower readings)
beside the control's (its upper ones).  With ``--kv-dtype`` the program
serves from its own quantized K/V pages, its own lower-precision path;
that path is then the control, judged alone, and ``checks`` shows which
number it fails.  Prints one JSON line per seed; the benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--kv-dtype")
    args = ap.parse_args(argv)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)   # as bench/run.py
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("bench/calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    engine = {"kv_dtype": args.kv_dtype} if args.kv_dtype else None
    seeds = [int(x) for x in args.seeds.split(",")]
    s = harness.setup(ROOT, args.workload, seeds[0],
                      harness.Options(engine=engine))
    for seed in seeds:
        harness.reseed(s, seed)
        control = not args.kv_dtype
        out = harness.measure(s, seed, args.seconds, False,
                              harness.Options(control=control), free=False)
        print(json.dumps({"seed": seed, "kv_dtype": args.kv_dtype,
                          "control": "fp8 reference" if control
                          else f"{args.kv_dtype} K/V pages",
                          "correct": out["correct"],
                          "readings": out.get("readings"),
                          "checks": out["checks"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
