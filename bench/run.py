#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
the run builds the served model from the seed, warms every program the
mix needs, offers the mix for ``--seconds``, and then checks the served
tokens against the float32 reference.  ``--trace 1`` wraps the window in
the JAX profiler and reports the per-layer metrics in place of the
end-to-end ones.

Earlier lines of standard output report what the run saw (compiles in
the window, engine counters, memory); the numbers that decide
``correct`` are the last lines of standard error; the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
``checks`` last.  Without a TPU, with fewer chips than the cell asks
for, or outside a checkout of the program, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"{ROOT} holds no program to run (no src/repro)")
    # the compile cache lives in this checkout (the program's default,
    # <checkout>/.jax_cache), never in a directory the machine names
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, registry

    try:
        cell = registry.load_cell(ROOT, args.workload)
    except KeyError as e:
        return fail(str(e))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        return fail(f"cell {cell.name} needs {cell.chips} chips; JAX found "
                    f"{len(devices)}")
    registry.peaks(devices[0].device_kind)       # unknown kind: an error
    out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
