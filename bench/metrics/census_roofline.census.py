"""The census's share of its roofline, in %, bounded by bytes alone.

The least time a census can take is the bytes it must read (six CSR rows
per connected pair, ``benchlib.peaks.census_bytes``, the same for any
implementation) over the chip's HBM bandwidth.  The share is that time
over the device time of the tile gather and the census kernel per census.
No operation term: no sourced int32 VPU peak is in the table.
"""

LAYERS = ("tile gather", "census kernel")

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"trace": {"layer_s": {LAYERS[0]: 6.0, LAYERS[1]: 2.0}},
                   "work": 4, "counters": {"necessary_bytes": 819e6}},
           "value": 0.05}


def read(ctx):
    need = ctx.counters.get("necessary_bytes")
    if ctx.trace is None or not ctx.work or not need:
        return None
    parts = [ctx.trace.layer_s.get(name) for name in LAYERS]
    if not all(parts):
        return None
    least = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(parts) / ctx.work)
