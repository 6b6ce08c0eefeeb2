"""The session loop's check, driven end to end on the CPU at scale 7, as
``test_bench_checks.py`` drives the census cell: a sound run is correct;
the control (the reference without its union test, in the program's
place) is wrong on every checked answer; a fault planted in the delta
path is caught.  The loop's stream and its necessary bytes are checked
against plain counts.

The harness's look for a chip is skipped: the runs call
``harness.run_cell`` directly, where the program resolves ``auto`` to
its xla backend.
"""
import functools
import json
import os
import time

import numpy as np
import pytest

from benchlib import cells, generators, harness, reference
from benchlib.mutations import Stream, delta_bytes

CELL = "graph500_s13.mutate"
GRAPH = {"kind": "kronecker", "scale": 7, "edge_factor": 8, "seed": 0}
# two arcs in and two out: at this scale a batch of eight touches most
# dyads and the program takes its full path, not the delta path
TRAFFIC = {"add": 2, "remove": 2, "held_back": 64, "warm_mutations": 2,
           "checked": 4}
SEED = 2 ** 31 + 77


def small_cell():
    """The cell's own configuration and traffic files, on a small graph."""
    w = cells.load_cell(CELL)

    def load(*path):
        with open(os.path.join(cells.BENCH, *path)) as f:
            return json.load(f)
    return cells.Cell(
        name=CELL, chips=1, config={**w.config, "graph": GRAPH},
        traffic={**load("traffic", "mutate.json"), **TRAFFIC},
        end_to_end=[{"name": m, "unit": "-"}
                    for m in ("census_s", "setup_s")],
        per_layer=[])


def run(control=None, seconds=1.0):
    from repro.engine import clear_plan_cache
    clear_plan_cache()
    return harness.run_cell(small_cell(), SEED, seconds, False,
                            t_start=time.perf_counter(),
                            expected_backend="xla", control=control,
                            compile_cache=False, log=lambda m: None)


def test_sound_run_is_correct_and_the_control_is_not():
    from repro.engine import plan_cache_stats
    control = functools.partial(reference.triad_census, dedup=False)
    r = run(control=control)
    plan, = plan_cache_stats()["entries"]
    assert plan["delta_runs"] > 0 and plan["delta_fulls"] == 0
    assert r["correct"] is True
    assert r["checks"] == {"wrong_answers": {"value": 0, "limit": 0}}
    assert r["attempted"] > TRAFFIC["checked"] and r["failed"] == 0
    assert r["control"]["checked"] == TRAFFIC["checked"]
    assert r["control"]["wrong_answers"] == r["control"]["checked"]
    assert set(r["metrics"]) == {"census_s", "setup_s"}


def _drop_an_affected_dyad(monkeypatch):
    """The delta pass forgets the last dyad of each affected set."""
    from repro.engine import delta
    orig = delta.affected_dyads

    def fewer(g, d):
        u, v = orig(g, d)
        return u[:-1], v[:-1]
    monkeypatch.setattr(delta, "affected_dyads", fewer)


def _correction_off_by_one(monkeypatch):
    """The fetched correction is one count off in its first bin."""
    from repro.engine import delta
    orig = delta._acc_fetch

    def off(plan, hi, lo):
        out = orig(plan, hi, lo)
        out[0] += 1
        return out
    monkeypatch.setattr(delta, "_acc_fetch", off)


@pytest.mark.parametrize("fault", [_drop_an_affected_dyad,
                                   _correction_off_by_one])
def test_a_fault_in_the_delta_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["wrong_answers"]["value"] > 0


def test_stream_keeps_the_arc_count_and_undoes_a_batch():
    n, src, dst = generators.kronecker(7, 8, seed=0)
    s = Stream(n, src, dst, 64, np.random.default_rng(0))
    before = s.snapshot()
    keys = {tuple(a) for a in np.concatenate(before)}
    i, j = s.draw(8, 8)
    added, removed = s.swap(i, j)
    assert len(s.present) == len(src) - 64 and len(s.pool) == 64
    assert {tuple(a) for a in np.concatenate(s.snapshot())} == keys
    present = {tuple(a) for a in s.present}
    assert all(tuple(a) in present for a in added)
    assert not any(tuple(a) in present for a in removed)
    s.swap(i, j)
    assert all(np.array_equal(a, b) for a, b in zip(s.snapshot(), before))


def test_delta_bytes_count_six_rows_per_touched_pair():
    n, src, dst = generators.kronecker(6, 4, seed=1)
    touched = np.array([int(src[0]), int(dst[-1])])
    out = {x: set() for x in range(n)}
    inn = {x: set() for x in range(n)}
    for a, b in zip(src.tolist(), dst.tolist()):
        out[a].add(b)
        inn[b].add(a)

    def rows(x):
        return len(out[x]) + len(inn[x]) + len(out[x] | inn[x])
    pairs = {(min(t, w), max(t, w)) for t in touched.tolist()
             for w in out[t] | inn[t]}
    want = 4 * sum(rows(u) + rows(v) for u, v in pairs)
    assert delta_bytes(n, src, dst, touched) == want
    assert delta_bytes(n, src, dst, []) == 0


def test_every_seed_mutates_the_same_arcs_in_its_own_labels():
    """The pool and the batches are drawn from the configuration's
    ``graph.seed`` and ``update_stream.seed``; ``--seed`` only relabels
    them, and the resident graph with its pool is the configuration's
    graph for that seed."""
    import types
    loop = cells.load_module("loops", "session")
    cell = small_cell()
    n = 1 << GRAPH["scale"]
    base = None
    for seed in (SEED, 5):
        run = types.SimpleNamespace(cell=cell, seed=seed,
                                    traffic=cell.traffic)
        s = loop._stream(run)
        want = generators.make_graph(GRAPH, seed)
        got = np.concatenate(s.snapshot())
        assert sorted(map(tuple, got.tolist())) == sorted(
            zip(want["src"].tolist(), want["dst"].tolist()))
        inv = np.argsort(np.random.default_rng([seed, 0]).permutation(n))
        batches = [s.swap(*s.draw(2, 2)) for _ in range(3)]
        unlabeled = [inv[s.pool]] + [inv[a] for b in batches for a in b]
        if base is None:
            base = unlabeled
        else:
            assert all(np.array_equal(a, b) for a, b in zip(base, unlabeled))


def test_the_cells_counter_readers_read_a_pallas_window():
    """The loop's counters, from a short window on the pallas path
    (interpret mode), give the cell's counter readers a number each: every
    mutation on the delta path, the delta path's host spans, and tile
    slots per necessary byte."""
    import types
    from repro.engine import clear_plan_cache
    clear_plan_cache()
    loop = cells.load_module("loops", "session")
    cell = small_cell()
    cell.traffic = {**cell.traffic, "engine": {"backend": "pallas"},
                    "warm_mutations": 1}
    run = harness.Run(cell=cell, seed=SEED, expected_backend="xla",
                      spans=harness.Spans(), log=lambda m: None)
    state = loop.setup(run)
    win = loop.window(run, state, time.perf_counter() + 0.5)
    counters = loop.counters(run, state, win)
    assert win.completed >= 1 and counters["delta_runs"] == win.completed
    assert counters["delta_chunks"] > 0 and counters["tile_slots"] > 0
    ctx = types.SimpleNamespace(work=win.completed, counters=counters)
    read = {m: cells.load_module("metrics", m).read(ctx)
            for m in ("delta_share.mutate", "delta_host_ms.mutate",
                      "gather_pad_ratio.census")}
    assert read["delta_share.mutate"] == 100.0
    assert read["delta_host_ms.mutate"] > 0
    assert read["gather_pad_ratio.census"] >= 1.0
