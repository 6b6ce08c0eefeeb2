#!/usr/bin/env python3
"""Record a profiler trace of warm censuses on the chip, and print what
the program's spans say about it.

    python3 bench/record_trace.py --scale 8 --censuses 2 \\
        --out bench/fixtures/census_rmat8_spans_v5e.xplane.pb.gz
    python3 bench/record_trace.py --scale 13 --censuses 2

The graph is the Graph500 Kronecker graph at ``--scale`` (edge factor
16, graph seed 0).  One census compiles every shape; then
``--censuses`` more run traced, each as the one-shot loop runs it
(``from_edges``, then ``compile(...).run``) inside the benchmark's spans
(``bench.window`` holding ``bench.from_edges`` and ``bench.census``).
With ``--out`` the trace is written there gzipped (a fixture for the
tests of the trace readers, ``bench/fixtures/``).  The last line on
standard output is one JSON object, per census: host ms and count of
each span (``benchlib.program_spans``), device ms of each tile width
``K`` with its tile gather and census kernel, and the idle ms by the
innermost span.  Exits non-zero without a TPU or when the plan does not
run the pallas kernels on it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--censuses", type=int, default=2)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from benchlib import cells, generators, harness, program_spans, trace
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    import jax
    from repro.core.graph import from_edges
    from repro.engine import EngineConfig, compile
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    n, src, dst = generators.kronecker(args.scale, 16, seed=0)
    config = EngineConfig(backend="auto")
    spans = harness.Spans()

    def census():
        with spans("from_edges"):
            g = from_edges(n, src, dst)
        with spans("census"):
            return compile(g, ["triad_census"], config).run(g)

    census()
    harness.check_plans("pallas")
    with trace.capture(os.path.join(cells.ROOT, ".bench_out",
                                    "record")) as path:
        with spans("window"):
            for _ in range(args.censuses):
                census()
    if args.out:
        with open(path[0], "rb") as f, gzip.open(args.out, "wb") as out:
            out.write(f.read())
    print(f"record_trace: {args.out}, n={n}, arcs={len(src)}",
          file=sys.stderr)
    layers = trace.load_layers(os.path.join(cells.BENCH, "layers"))
    print(json.dumps(report(program_spans.load(path[0]), layers,
                            args.censuses)))
    return 0


def report(pt, layers, censuses: int) -> dict:
    """Per census: ms and count of each span, device ms by tile width
    (None where the pairing does not hold), idle ms by span."""
    from benchlib import program_spans, trace
    r = program_spans.read(pt, layers, devices=[trace.device_plane(0)])
    ms = 1e3 / censuses
    by_k = None
    if r.bucket_s is not None:
        by_k = {k: {"device_ms": s * ms,
                    **{layer: v * ms
                       for layer, v in r.bucket_layer_s.get(k, {}).items()}}
                for k, s in sorted(r.bucket_s.items())}
    return {"censuses": censuses,
            "span_ms": {k: [s * ms, c] for k, (s, c) in
                        sorted(r.span_s.items())},
            "bucket_ms": by_k,
            "idle_ms": {k: v * ms for k, v in r.idle_gaps}}


if __name__ == "__main__":
    sys.exit(main())
