#!/usr/bin/env python3
"""Run one cell of the triad-census benchmark once, on the chip(s) here.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  Its graph is made from the
seed; set-up builds the program's objects and warms every shape the
window uses; the window drives the program for ``--seconds``; then every
answer the window produced (or a seeded sample of them) is compared with
the plain reference in ``bench/benchlib/reference.py``.  The last line on
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer metrics read from a profiler trace of the window),
``device`` and, last, ``checks``: each number compared with its limit.
The same numbers are the last lines on standard error.

Exits non-zero with no result line when JAX finds no TPU or fewer chips
than the cell asks for, when the program is not beside the benchmark, and
on any fallback: a demoted plan, a quarantined device, interpret mode.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib import cells, harness
    try:
        cell = cells.load_cell(args.workload)
    except (FileNotFoundError, KeyError) as e:
        err(f"bench: {e}")
        return 2
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    try:
        import jax
        import repro.engine  # noqa: F401  the program under test
    except ImportError as e:
        err(f"bench: cannot import the program: {e}")
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        err(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
            f"{len(devices)} {devices[0].platform!r} device(s)")
        return 1
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  expected_backend="pallas", log=err)
    except harness.Fallback as e:
        err(f"bench: fallback: {e}")
        return 1
    for name, c in result["checks"].items():
        err(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
