"""Share of the traced window in which no operation ran on the device,
in %: one minus the union of the device-operation intervals over the
window, averaged over the chips used."""

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"trace": {"window_s": 10.0, "busy_s": 9.0}}, "value": 10.0}


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
