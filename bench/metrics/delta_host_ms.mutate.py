"""Host time per mutation of the delta path's own host steps, in ms: the
program's spans ``repro.apply_csr`` (the mutated CSR), ``repro.affected``
(both affected-dyad sets) and ``repro.delta_schedule`` (the subset
passes' bucket sort), from its span tally over the window, as the loop
reports it (``span_s.<name>``).  A program without the tally reads
nothing."""

SPANS = ("apply_csr", "affected", "delta_schedule")

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"counters": {"span_s.apply_csr": 2.4,
                                "span_s.affected": 0.3,
                                "span_s.delta_schedule": 0.3},
                   "work": 60},
           "value": 50.0}


def read(ctx):
    parts = [ctx.counters.get("span_s." + name) for name in SPANS]
    if any(p is None for p in parts) or not ctx.work:
        return None
    return 1e3 * sum(parts) / ctx.work
