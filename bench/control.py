#!/usr/bin/env python3
"""Readings of the check's two ends, for setting its limit: the program's
``wrong_answers`` and the control's, on the same window, for many seeds.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

The control is the plain reference without its union test
(``reference.triad_census(..., dedup=False)``), put in the program's
place: every answer the window produced is replaced by the control's
answer for the same graph, then judged like the program's.  One process
runs every seed; each prints one JSON line.  The benchmark's own runs
(``bench/run.py``) never run the control.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchlib import cells, harness, reference
    cell = cells.load_cell(args.workload)
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 1
    control = functools.partial(reference.triad_census, dedup=False)
    for seed in args.seeds:
        r = harness.run_cell(cell, seed, args.seconds, False,
                             t_start=time.perf_counter(),
                             expected_backend="pallas", control=control,
                             log=lambda m: print(m, file=sys.stderr))
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program": r["checks"]["wrong_answers"]["value"],
                          "control": r["control"]["wrong_answers"],
                          "checked": r["control"]["checked"],
                          "attempted": r["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
