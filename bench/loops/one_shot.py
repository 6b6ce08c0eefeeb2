"""One-shot censuses, closed loop, one caller.

Each step builds a fresh graph from the seed's arc list with
``from_edges`` (so no per-graph memo of the program hits) and runs
``compile(g, ["triad_census"], EngineConfig(**engine)).run(g)``; steps
run back to back.  Traffic keys: ``engine`` (EngineConfig arguments).

End to end: ``census_s``, the window up to the last completion divided by
the censuses completed.  Every census of the window is checked.
"""
from __future__ import annotations

import time

from benchlib import peaks
from benchlib.harness import Answer, Window


def _census(run, state):
    from repro.core.graph import from_edges
    from repro.engine import compile
    n, src, dst = state["arcs"]
    with run.spans("from_edges"):
        g = from_edges(n, src, dst)
    with run.spans("census"):
        plan = compile(g, ["triad_census"], state["engine"])
        return plan.run(g)["triad_census"].counts


def setup(run):
    graph = run.data
    state = {"arcs": (graph["n"], graph["src"], graph["dst"]),
             "engine": run.engine_config()}
    _census(run, state)                      # compiles every shape
    return state


def window(run, state, deadline):
    t0 = time.perf_counter()
    answers = []
    while time.perf_counter() < deadline:
        try:
            counts = _census(run, state)
        except Exception as e:  # a census that fails is an answer missing
            run.log(f"census failed: {e!r}")
            counts = None
        answers.append(counts)
    t_end = time.perf_counter()
    return Window(t0=t0, t_end=t_end, attempted=len(answers),
                  completed=sum(c is not None for c in answers),
                  data={"answers": answers})


def end_to_end(run, state, win):
    return {"census_s": (win.t_end - win.t0) / max(win.completed, 1)}


def counters(run, state, win):
    return {"necessary_bytes": peaks.census_bytes(*state["arcs"])}


def answers(run, state, win):
    arcs = state["arcs"]
    return [Answer(key=0, arcs=lambda: arcs, got=c)
            for c in win.data["answers"]]
