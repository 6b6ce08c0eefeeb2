"""Host time of ``from_edges`` per census, in ms: the benchmark's own span
around the CSR build of each step."""

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"spans": {"from_edges": [0.02, 0.04]}}, "value": 30.0}


def read(ctx):
    s = ctx.spans.get("from_edges")
    return 1e3 * sum(s) / len(s) if s else None
