"""Share of the window's mutations that the program served by the delta
correction, in %: the window's difference of the plans' ``delta_runs``
over the mutations completed.  The rest were full recomputes or
recompiles."""

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"counters": {"delta_runs": 57}, "work": 60},
           "value": 95.0}


def read(ctx):
    runs = ctx.counters.get("delta_runs")
    return 100.0 * runs / ctx.work if runs is not None and ctx.work else None
