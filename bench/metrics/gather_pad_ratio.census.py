"""Bytes the tile gather writes per byte the census must read (x): the
program's counter ``tile_slots`` (six ``(chunk, K)`` int32 tiles per
chunk, padding included) per census, at 4 bytes a slot, over the
necessary bytes of one census (``benchlib.peaks.census_bytes``).

Tile slots per census: the loop's counters over the window where they
hold ``tile_slots``; else the program's plans
(``repro.engine.plan_cache_stats()``), their ``tile_slots`` over their
census runs.  The one-shot loop censuses one graph throughout, so every
run of its plan gathers the same slots and the two agree.  A program
that keeps no such counter reads nothing."""

# A context the reader reads, and the number it gives there.
EXAMPLE = {"ctx": {"counters": {"tile_slots": 3e9, "necessary_bytes": 1e9},
                   "work": 2},
           "value": 6.0}


def plans_slots_per_census():
    """Tile slots per census run over the program's cached plans, or
    None where no plan counts them."""
    try:
        from repro.engine import plan_cache_stats
    except ImportError:
        return None
    counted = [e for e in plan_cache_stats()["entries"] if "tile_slots" in e]
    slots = sum(e["tile_slots"] for e in counted)
    runs = sum(e["runs"] for e in counted)
    return slots / runs if slots and runs else None


def read(ctx):
    need = ctx.counters.get("necessary_bytes")
    if not (need and ctx.work):
        return None
    slots = ctx.counters.get("tile_slots")
    per_census = slots / ctx.work if slots else plans_slots_per_census()
    return None if per_census is None else 4.0 * per_census / need
