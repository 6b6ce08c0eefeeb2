"""Batched serving layer: run_batch bit-identity, batching policy,
out-of-order completion, per-bucket stats, cache-entry metadata, and
the spans a session's mutations tally."""
import dataclasses

import numpy as np
import pytest

from repro.core import brute_force_census, from_edges, generators
from repro.engine import (CensusConfig, GraphMeta, clear_plan_cache,
                          compile_census, plan_cache_stats)
from repro.serve import CensusCompletion, CensusService, ServiceConfig

CFG = CensusConfig(backend="xla", batch=16, chunk_dyads=64)


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _same_bucket(make, n, k=None):
    """First n generated graphs sharing the modal GraphMeta bucket."""
    groups = {}
    for seed in range(8 * n):
        g = make(seed)
        groups.setdefault(GraphMeta.from_graph(g, k=k), []).append(g)
        best = max(groups.values(), key=len)
        if len(best) >= n:
            return best[:n]
    raise AssertionError("could not assemble a same-bucket fleet")


# ----------------------------------------------------------------------------
# CensusPlan.run_batch
# ----------------------------------------------------------------------------

def test_run_batch_bit_identical_to_sequential():
    """The acceptance criterion: B same-bucket graphs through run_batch
    == B sequential plan.run calls, bit for bit (and == the oracle)."""
    fleet = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 5, k=CFG.k)
    plan = compile_census(fleet[0], CFG)
    batched = plan.run_batch(fleet)
    for got, g in zip(batched, fleet):
        want = plan.run(g)
        assert (got.counts == want.counts).all()
        assert got.counts.dtype == want.counts.dtype == np.int64
        assert (got.counts == brute_force_census(g).counts).all()
    assert plan.stats["batch_runs"] == 1
    assert plan.stats["batch_graphs"] == len(fleet)


def test_run_batch_b1_matches_run():
    g = generators.rmat(6, edge_factor=4, seed=0)
    plan = compile_census(g, CFG)
    assert (plan.run_batch([g])[0].counts == plan.run(g).counts).all()


def test_run_batch_mixed_sizes_same_bucket():
    """Graphs of different true size (same buckets) batch correctly,
    including a zero-dyad graph whose result is the closed form only."""
    g = generators.rmat(6, edge_factor=4, seed=0)
    empty = from_edges(5, [], [])
    tiny = from_edges(4, [0, 1], [1, 2])
    plan = compile_census(g, CFG)
    out = plan.run_batch([empty, g, tiny])
    assert out[0].counts[0] == 5 * 4 * 3 // 6
    assert out[0].counts[1:].sum() == 0
    assert (out[1].counts == plan.run(g).counts).all()
    assert (out[2].counts == brute_force_census(tiny).counts).all()


def test_run_batch_empty_list_and_admission():
    g = generators.rmat(6, edge_factor=2, seed=0)
    plan = compile_census(g, CFG)
    assert plan.run_batch([]) == []
    g_big = generators.rmat(9, edge_factor=8, seed=0)
    with pytest.raises(ValueError, match="recompile"):
        plan.run_batch([g, g_big])


def test_run_batch_one_transfer_per_batch():
    """B graphs, one device->host sync (the dispatch amortization)."""
    fleet = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 4, k=CFG.k)
    plan = compile_census(fleet[0], CFG)
    s0 = plan.stats["host_syncs"]
    plan.run_batch(fleet)
    assert plan.stats["host_syncs"] == s0 + 1


@pytest.mark.parametrize("backend", ["pallas", "distributed"])
def test_run_batch_fallback_backends(backend):
    """Backends without a vmapped unit fall back member-wise — same
    results, same API."""
    g1 = generators.rmat(6, edge_factor=4, seed=0)
    g2 = generators.rmat(6, edge_factor=4, seed=1)
    plan = compile_census(g1, CensusConfig(backend=backend, batch=16,
                                           chunk_dyads=256))
    plan._check(g2)  # same bucket by construction of the seeds above
    out = plan.run_batch([g1, g2])
    assert (out[0].counts == brute_force_census(g1).counts).all()
    assert (out[1].counts == brute_force_census(g2).counts).all()


# ----------------------------------------------------------------------------
# CensusService batching policy
# ----------------------------------------------------------------------------

def test_service_results_match_oracle_and_ids_are_stable():
    svc = CensusService(ServiceConfig(max_batch=4, max_wait_requests=100,
                                      census=CFG))
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in range(7)]
    ids = [svc.submit(g) for g in fleet]
    assert ids == list(range(7))
    done = {c.request_id: c.result for c in svc.flush()}
    assert sorted(done) == ids and svc.pending == 0
    for i, g in zip(ids, fleet):
        assert (done[i].counts == brute_force_census(g).counts).all()


def test_service_flushes_full_batches_eagerly():
    """A bucket group executes inside submit() as soon as it fills."""
    fleet = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 4, k=CFG.k)
    svc = CensusService(ServiceConfig(max_batch=2, max_wait_requests=100,
                                      census=CFG))
    svc.submit(fleet[0])
    assert svc.pending == 1 and not svc.poll()
    svc.submit(fleet[1])  # fills the bucket -> runs now
    done = svc.poll()
    assert [c.request_id for c in done] == [0, 1]
    assert svc.pending == 0
    assert all(isinstance(c, CensusCompletion) for c in done)


def test_service_out_of_order_completion():
    """A late-arriving bucket can complete before an earlier request."""
    a = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 2, k=CFG.k)
    b = from_edges(4, [0, 1], [1, 2])  # a different (tiny) bucket
    svc = CensusService(ServiceConfig(max_batch=2, max_wait_requests=100,
                                      census=CFG))
    svc.submit(b)          # rid 0, waits (bucket of one)
    svc.submit(a[0])       # rid 1
    svc.submit(a[1])       # rid 2 -> fills a's bucket, completes first
    assert [c.request_id for c in svc.poll()] == [1, 2]
    assert [c.request_id for c in svc.flush()] == [0]


def test_service_max_wait_requests_bounds_staleness():
    """A partial group is force-flushed once max_wait newer requests
    passed it — no bucket waits forever behind hot ones."""
    slow = from_edges(4, [0, 1], [1, 2])
    hot = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 3, k=CFG.k)
    svc = CensusService(ServiceConfig(max_batch=100, max_wait_requests=2,
                                      census=CFG))
    rid = svc.submit(slow)
    svc.submit(hot[0])
    assert not [c for c in svc.poll() if c.request_id == rid]
    svc.submit(hot[1])  # 2 newer than rid -> next submit flushes it
    done = svc.poll()
    assert any(c.request_id == rid for c in done)


def test_service_hot_bucket_burst_fills_to_max_batch():
    """Staleness counts other-bucket arrivals only: a hot bucket's own
    burst is never force-flushed below max_batch."""
    hot = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 4, k=CFG.k)
    svc = CensusService(ServiceConfig(max_batch=4, max_wait_requests=1,
                                      census=CFG))
    for g in hot[:3]:
        svc.submit(g)
        assert not svc.poll()  # still batching despite max_wait=1
    svc.submit(hot[3])  # fills max_batch -> one full-width batch
    assert len(svc.poll()) == 4
    meta = GraphMeta.from_graph(hot[0], k=CFG.k)
    assert svc.stats()["buckets"][meta]["occupancy"] == 1.0


def test_run_fleet_preserves_prior_pending_completions():
    """run_fleet must not swallow completions of requests submitted
    before it — they stay queued for the next poll()."""
    early = from_edges(4, [0, 1], [1, 2])
    svc = CensusService(ServiceConfig(max_batch=8, max_wait_requests=100,
                                      census=CFG))
    rid = svc.submit(early)
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in range(3)]
    out = svc.run_fleet(fleet)
    assert len(out) == 3
    held = svc.poll()
    assert [c.request_id for c in held] == [rid]
    assert (held[0].result.counts == brute_force_census(early).counts).all()


def test_service_max_wait_zero_is_unbatched():
    svc = CensusService(ServiceConfig(max_batch=8, max_wait_requests=0,
                                      census=CFG))
    g = generators.rmat(6, edge_factor=4, seed=0)
    rid = svc.submit(g)
    done = svc.poll()
    assert [c.request_id for c in done] == [rid]  # flushed immediately


def test_service_stats_and_cache_entries():
    fleet = _same_bucket(
        lambda s: generators.rmat(6, edge_factor=4, seed=s), 4, k=CFG.k)
    svc = CensusService(ServiceConfig(max_batch=4, max_wait_requests=100,
                                      census=CFG))
    svc.run_fleet(fleet)
    st = svc.stats()
    assert st["requests"] == 4 and st["batches"] == 1
    assert st["mean_batch"] == 4.0
    meta = GraphMeta.from_graph(fleet[0], k=CFG.k)
    bucket = st["buckets"][meta]
    assert bucket["occupancy"] == 1.0
    assert bucket["host_syncs"] == 1  # one transfer served all 4 requests
    # plan_cache_stats carries the per-bucket entry metadata the service
    # (and dashboards) read: bucket fields + live counters.
    entries = plan_cache_stats()["entries"]
    assert len(entries) == 1
    e = entries[0]
    assert e["meta"]["n_bucket"] == meta.n_bucket
    assert e["backend"] == "xla" and e["batch_runs"] == 1
    assert e["runs"] == 4 and e["device_path"] is True


def test_service_config_validation():
    with pytest.raises(ValueError):
        ServiceConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServiceConfig(max_wait_requests=-1)


def test_run_fleet_returns_input_order():
    svc = CensusService(ServiceConfig(max_batch=3, census=CFG))
    fleet = [generators.rmat(6, edge_factor=4, seed=s) for s in range(5)]
    out = svc.run_fleet(fleet)
    assert len(out) == 5
    for res, g in zip(out, fleet):
        assert (res.counts == brute_force_census(g).counts).all()


# ----------------------------------------------------------------------------
# subscribed sessions: the spans' tally of what a mutation does
# ----------------------------------------------------------------------------

def _tally_window(before, after):
    return {k: c - before.get(k, (0.0, 0))[1] for k, (_, c) in after.items()
            if c != before.get(k, (0.0, 0))[1]}


def test_session_mutations_tally_their_spans_by_mode():
    from repro.core import GraphDelta
    from repro.core.spans import span_totals
    svc = CensusService(ServiceConfig(
        census=dataclasses.replace(CFG, delta_threshold=1.0)))
    g = from_edges(32, [0, 1, 2, 3], [1, 2, 3, 4])
    sid = svc.subscribe(g)
    before = span_totals()
    ack = svc.mutate(sid, GraphDelta(edges_added=[(4, 5)],
                                     edges_removed=[(0, 1)]))
    assert ack["mode"] == "delta"
    calls = _tally_window(before, span_totals())
    assert {k: calls.get(k) for k in ("mutate", "delta", "apply_csr",
                                      "affected", "delta_fold", "fetch")} \
        == {"mutate": 1, "delta": 1, "apply_csr": 1, "affected": 2,
            "delta_fold": 1, "fetch": 1}
    # outgrowing the plan's buckets recompiles: the CSR is rebuilt, and
    # no correction runs
    before = span_totals()
    hub = GraphDelta(edges_added=[(0, v) for v in range(5, 31)])
    assert svc.mutate(sid, hub)["mode"] == "recompile"
    calls = _tally_window(before, span_totals())
    assert calls["mutate"] == 1 and "delta_fold" not in calls
    assert calls["apply_csr"] >= 1
    cur = svc._sessions[sid].graph
    assert (svc.poll(sid).counts == brute_force_census(cur).counts).all()
