"""Device time of the tile gather per census, in ms: the trace's
operations of the layer "tile gather" (bench/layers/tile_gather.*.json)
over the censuses completed in the traced window."""

LAYER = "tile gather"

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"trace": {"layer_s": {LAYER: 6.0}}, "work": 4},
           "value": 1500.0}


def read(ctx):
    s = ctx.trace.layer_s.get(LAYER) if ctx.trace else None
    return 1e3 * s / ctx.work if s and ctx.work else None
