"""A live mutation session, closed loop, one caller.

Set-up draws ``held_back`` arcs uniformly from the configuration's arc
list, subscribes the rest through
``CensusService(ServiceConfig(census=EngineConfig(**engine)))`` and runs
``warm_mutations`` steps.  Each step removes ``remove`` arcs drawn
uniformly from the present arcs and adds ``add`` drawn uniformly from
the pool (``benchlib.mutations.Stream``: removed arcs join the pool, so
the arc count stays fixed), calls ``mutate(sid, GraphDelta(...))`` and
reads ``poll(sid).counts``.  Traffic keys: ``engine``, ``add``,
``remove``, ``held_back``, ``warm_mutations``, ``checked``.

The pool and every batch are drawn once, from the configuration's
``graph.seed`` and ``update_stream.seed``, over the arcs in the
generator's order, as a dataset's update stream is written once beside
its snapshot; ``--seed`` relabels the vertices (as
``generators.make_graph`` does, without re-sorting the arcs) and picks
the checked polls.  So every seed holds
back the same arcs and makes the same batches in its own labels, and
does the same work: batches drawn per seed differ in their hub dyads,
and their means over a window differ by more than the cell's bound.

End to end: ``census_s``, the window up to the last completion divided by
the mutations completed: the wall time from a batch of edits to its
updated 16 counts on the host.

Answers: a seeded sample of ``checked`` polls, the window's last one
among them, each with the arc list the loop itself tracked (replayed
from the window's first state, never read back from the program).
Each poll derives from the previous raw bins, so an error in any
correction of the window persists into the last answer.

Counters: ``necessary_bytes`` per mutation (``benchlib.mutations.
delta_bytes`` over both graphs of each mutation, averaged over the
window's mutations, as the one-shot loop reports it per census), and the
window's difference of the program's plan counters (``delta_runs``,
``delta_fulls``, ``delta_affected``, ``delta_chunks``, ``tile_slots``)
and of its span tally (``span_s.<name>``, ``span_calls.<name>``).  What
the program does not keep is absent, not zero.
"""
from __future__ import annotations

import json
import time

import numpy as np

from benchlib import generators
from benchlib.harness import Answer, Window
from benchlib.mutations import Stream, delta_bytes

PLAN_COUNTERS = ("delta_runs", "delta_fulls", "delta_affected",
                 "delta_chunks", "tile_slots")


def _rng(run, stream: int) -> np.random.Generator:
    return np.random.default_rng([run.seed % (1 << 64), stream])


def _program_counters() -> dict:
    """The plan counters summed over the program's plans, and its span
    tally; a name the program does not keep is left out."""
    from repro.engine import plan_cache_stats
    entries = plan_cache_stats()["entries"]
    out = {k: sum(e[k] for e in entries) for k in PLAN_COUNTERS
           if entries and all(k in e for e in entries)}
    try:
        from repro.core.spans import span_totals
    except ImportError:
        return out
    for name, (seconds, calls) in span_totals().items():
        out["span_s." + name] = seconds
        out["span_calls." + name] = calls
    return out


def _step(run, state):
    """One batch: draw, mutate, poll.  Returns ``(counts, (i, j))``: the
    counts are None where a call failed, and a failed mutation is undone
    in the loop's own arc list too (the session rolls back), its batch
    None."""
    from repro.core import GraphDelta
    stream, t = state["stream"], run.traffic
    i, j = stream.draw(t["add"], t["remove"])
    added, removed = stream.swap(i, j)
    try:
        with run.spans("mutate"):
            state["svc"].mutate(state["sid"], GraphDelta(
                edges_added=added, edges_removed=removed))
    except Exception as e:  # a mutation that fails is an answer missing
        run.log(f"mutation failed: {e!r}")
        stream.swap(i, j)
        return None, None
    try:
        with run.spans("poll"):
            return np.asarray(state["svc"].poll(state["sid"]).counts), (i, j)
    except Exception as e:
        run.log(f"poll failed: {e!r}")
        return None, (i, j)


def _stream(run) -> Stream:
    """The configuration's arcs in the generator's order, relabeled by
    ``--seed``, with the pool and the batches drawn from ``graph.seed``
    and ``update_stream.seed``."""
    spec = run.cell.config["graph"]
    stream_seed = run.cell.config["update_stream"]["seed"]
    if spec["kind"] != "kronecker":
        raise ValueError(f"unknown graph kind {spec['kind']!r}")
    n, src, dst = generators.kronecker(spec["scale"], spec["edge_factor"],
                                       seed=spec["seed"])
    src, dst = generators.relabel(n, src, dst, _rng(run, 0))
    return Stream(n, src, dst, run.traffic["held_back"],
                  np.random.default_rng([spec["seed"], stream_seed]))


def setup(run):
    from repro.core.graph import from_edges
    from repro.serve import CensusService, ServiceConfig
    t = run.traffic
    stream = _stream(run)
    svc = CensusService(ServiceConfig(census=run.engine_config()))
    with run.spans("subscribe"):
        sid = svc.subscribe(from_edges(*stream.arcs()))
    state = {"stream": stream, "svc": svc, "sid": sid}
    for _ in range(t["warm_mutations"]):   # compiles every shape
        _step(run, state)
    return state


def window(run, state, deadline):
    first = state["stream"].snapshot()
    before = _program_counters()
    t0 = time.perf_counter()
    answers, batches = [], []
    while time.perf_counter() < deadline:
        counts, batch = _step(run, state)
        answers.append(counts)
        batches.append(batch)
    t_end = time.perf_counter()
    after = _program_counters()
    counters = {k: v - before.get(k, 0) for k, v in after.items()}
    return Window(t0=t0, t_end=t_end, attempted=len(answers),
                  completed=sum(c is not None for c in answers),
                  data={"answers": answers, "batches": batches,
                        "first": first, "counters": counters})


def _replay(state, win):
    """The loop's arc list after each batch of the window, in order:
    yields ``(position, stream, touched)``, the stream at that state."""
    stream = state["stream"]
    stream.restore(win.data["first"])
    for pos, batch in enumerate(win.data["batches"]):
        touched = None
        if batch is not None:
            added, removed = stream.swap(*batch)
            touched = np.unique(np.concatenate([added, removed]).ravel())
        yield pos, stream, touched


def end_to_end(run, state, win):
    return {"census_s": (win.t_end - win.t0) / max(win.completed, 1)}


def counters(run, state, win):
    out = dict(win.data["counters"])
    first = win.data["first"][0]
    prev = (state["stream"].n, first[:, 0], first[:, 1])
    need, mutations = 0, 0
    for _, stream, touched in _replay(state, win):
        arcs = stream.arcs()
        if touched is not None:     # the batch's old graph, then its new
            need += delta_bytes(*prev, touched) + delta_bytes(*arcs, touched)
            mutations += 1
        prev = arcs
    if mutations:
        out["necessary_bytes"] = need / mutations
    run.log(f"counters {json.dumps(out, sort_keys=True)}")
    return out


def answers(run, state, win):
    n = len(win.data["answers"])
    if not n:
        return []
    k = min(run.traffic["checked"], n) - 1
    picked = set(_rng(run, 2).choice(n - 1, size=k, replace=False).tolist())
    picked.add(n - 1)
    out = []
    for pos, stream, _ in _replay(state, win):
        if pos in picked:
            arcs = stream.arcs()
            out.append(Answer(key=pos, arcs=lambda a=arcs: a,
                              got=win.data["answers"][pos]))
    return out
