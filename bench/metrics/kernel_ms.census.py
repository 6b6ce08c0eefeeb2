"""Device time of the census kernel per census, in ms: the trace's
operations of the layer "census kernel" (bench/layers/census_kernel.*.json)
over the censuses completed in the traced window."""

LAYER = "census kernel"

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"trace": {"layer_s": {LAYER: 2.0}}, "work": 4},
           "value": 500.0}


def read(ctx):
    s = ctx.trace.layer_s.get(LAYER) if ctx.trace else None
    return 1e3 * s / ctx.work if s and ctx.work else None
