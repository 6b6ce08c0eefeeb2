"""Fault injection + recovery: deterministic FaultPlan replay, bounded
chunk retry with bit-identical recovered results on every backend and
schedule, device-loss quarantine and the dynamic→static rung, the
pallas→xla compile/runtime rungs, poison-batch isolation and admission
control in the serve layer, session rollback on mid-mutate failure, and
the REPRO_FAULT_PLAN environment hook — all clockless and seeded, so
every failing scenario replays exactly."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import brute_force_census, generators
from repro.core.delta import GraphDelta
from repro.engine import (ChunkRetryError, DeviceLostError, EngineConfig,
                          FaultPlan, InjectedFault, WorkerFailures,
                          clear_plan_cache, compile, is_poisoned,
                          plan_cache_stats, poison, resolve_faults, unpoison)
from repro.engine.executor import _raise_worker_errors
from repro.serve import (AdmissionError, CensusService, DeadlineExceeded,
                         ServiceConfig)

BACKENDS = ["xla", "pallas", "distributed"]
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: explicit inert plan: opts OUT of any REPRO_FAULT_PLAN chaos-CI
#: environment plan, so "clean" baselines stay clean under chaos runs.
CLEAN = FaultPlan()

#: recoverable chunk chaos: every selected chunk fails exactly its first
#: attempt (fail_attempts=1 < max_attempts default 3), so recovery is
#: deterministic and total.
CHAOS = FaultPlan(seed=3, chunk_failure_rate=0.5, fail_attempts=1)


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _graph():
    return generators.rmat(7, edge_factor=4, seed=11)


# ----------------------------------------------------------------------------
# FaultPlan mechanics: validation, determinism, inertness, resolution
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(chunk_failure_rate=1.5), "chunk_failure_rate"),
    (dict(slow_chunk_rate=-0.1), "slow_chunk_rate"),
    (dict(fail_attempts=0), "fail_attempts"),
    (dict(device_loss=(-1,)), "device_loss"),
    (dict(device_loss_after=-1), "device_loss_after"),
    (dict(compile_failure=("cuda",)), "unknown backends"),
    (dict(runtime_failure=("nope",)), "unknown backends"),
    (dict(mutate_failure_calls=(-2,)), "mutate_failure_calls"),
    (dict(slow_s=-1.0), "slow_s"),
])
def test_fault_plan_knob_validation(kwargs, match):
    with pytest.raises(ValueError, match=match):
        FaultPlan(**kwargs)


@pytest.mark.parametrize("kwargs,match", [
    (dict(max_attempts=0), "max_attempts"),
    (dict(backend_fallback="yes"), "backend_fallback"),
    (dict(schedule_fallback=1), "schedule_fallback"),
    (dict(fault_plan="chaos"), "fault_plan"),
])
def test_engine_config_fault_knob_validation(kwargs, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**kwargs)


def test_fault_plan_is_deterministic_and_hashable():
    a = FaultPlan(seed=9, chunk_failure_rate=0.3, device_loss=[1, 2])
    b = FaultPlan(seed=9, chunk_failure_rate=0.3, device_loss=(1, 2))
    assert a == b and hash(a) == hash(b)  # list input normalized to tuple
    # pure counter hash: same (seed, chunk) decision from any instance,
    # any call order, no RNG state consumed anywhere.
    decisions = [a.chunk_fails(s, 1) for s in range(0, 4096, 64)]
    assert decisions == [b.chunk_fails(s, 1) for s in range(0, 4096, 64)]
    assert any(decisions) and not all(decisions)
    # a different seed is a different schedule
    c = FaultPlan(seed=10, chunk_failure_rate=0.3)
    assert decisions != [c.chunk_fails(s, 1) for s in range(0, 4096, 64)]
    # attempts past fail_attempts succeed (the recoverability contract)
    start = next(s for s in range(0, 4096, 64) if a.chunk_fails(s, 1))
    assert not a.chunk_fails(start, 2)


def test_inert_plan_resolution_and_env_opt_out():
    assert FaultPlan().is_inert
    assert not CHAOS.is_inert
    # an explicitly inert plan resolves to None (skip injection checks
    # entirely), a live plan resolves to itself.
    assert resolve_faults(CLEAN) is None
    assert resolve_faults(CHAOS) is CHAOS


def test_poison_registry_is_identity_based():
    g, twin = _graph(), _graph()
    poison(g)
    try:
        assert is_poisoned(g)
        assert not is_poisoned(twin)  # structurally equal copy unaffected
    finally:
        unpoison(g)
    assert not is_poisoned(g)


# ----------------------------------------------------------------------------
# recovery: retried runs are bit-identical to fault-free, one sync
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("schedule", ["static", "dynamic"])
def test_recovered_run_bit_identical_one_sync(backend, schedule):
    g = _graph()
    want = brute_force_census(g).counts
    cfg = EngineConfig(backend=backend, batch=16, chunk_dyads=64,
                       schedule=schedule, fault_plan=CHAOS)
    plan = compile(g, ("triad_census", "dyad_census"), cfg)
    res = plan.run(g)
    assert np.array_equal(res["triad_census"].counts, want)
    fs = plan.stats["faults"]
    assert fs["chunk_failures"] > 0, "chaos plan never fired — dead test"
    assert fs["retries"] > 0
    assert plan.stats["host_syncs"] == 1  # recovery costs no extra sync
    assert sum(plan.stats["device_chunks"].values()) == plan.stats["chunks"]
    # bit-identity against an explicitly clean plan
    clean = compile(g, ("triad_census", "dyad_census"),
                    EngineConfig(backend=backend, batch=16, chunk_dyads=64,
                                 schedule=schedule, fault_plan=CLEAN))
    clean_res = clean.run(g)
    assert np.array_equal(res["triad_census"].counts,
                          clean_res["triad_census"].counts)
    assert res["dyad_census"] == clean_res["dyad_census"]
    assert clean.stats["faults"]["chunk_failures"] == 0


def test_same_seed_replays_identical_fault_trace():
    g = _graph()
    traces = []
    for _ in range(2):
        clear_plan_cache()  # force a fresh plan (same config = same entry)
        plan = compile(g, ("triad_census",),
                       EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                                    fault_plan=CHAOS))
        plan.run(g)
        traces.append((list(plan.stats["fault_events"]),
                       dict(plan.stats["faults"])))
    # static schedule: the whole trace — order included — replays exactly
    assert traces[0] == traces[1]
    assert any(e[0] == "chunk_failure" for e in traces[0][0])


def test_retry_exhaustion_raises_chunk_retry_error():
    g = _graph()
    # fail_attempts >= max_attempts: the selected chunks can never recover
    cfg = EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                       max_attempts=2,
                       fault_plan=FaultPlan(seed=3, chunk_failure_rate=0.5,
                                            fail_attempts=99))
    plan = compile(g, ("triad_census",), cfg)
    with pytest.raises(ChunkRetryError) as exc:
        plan.run(g)
    assert len(exc.value.attempts) == 2  # the full dispatch budget
    assert isinstance(exc.value.__cause__, InjectedFault)


def test_max_attempts_one_disables_retry():
    g = _graph()
    cfg = EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                       max_attempts=1, fault_plan=CHAOS)
    plan = compile(g, ("triad_census",), cfg)
    with pytest.raises(ChunkRetryError):
        plan.run(g)
    assert plan.stats["faults"]["retries"] == 0


# ----------------------------------------------------------------------------
# the degradation ladder
# ----------------------------------------------------------------------------

def test_device_loss_takes_dynamic_to_static_rung():
    g = _graph()
    want = brute_force_census(g).counts
    cfg = EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                       schedule="dynamic", n_executor_devices=1,
                       fault_plan=FaultPlan(seed=1, device_loss=(0,)))
    plan = compile(g, ("triad_census",), cfg)
    res = plan.run(g)
    assert np.array_equal(res["triad_census"].counts, want)
    fs = plan.stats["faults"]
    assert fs["device_losses"] >= 1
    assert fs["schedule_fallbacks"] == 1
    assert plan.stats["host_syncs"] == 1  # the rung restarts, then 1 sync
    assert any(e[0] == "schedule_fallback"
               for e in plan.stats["fault_events"])


def test_schedule_fallback_disabled_surfaces_the_loss():
    g = _graph()
    cfg = EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                       schedule="dynamic", n_executor_devices=1,
                       schedule_fallback=False,
                       fault_plan=FaultPlan(seed=1, device_loss=(0,)))
    plan = compile(g, ("triad_census",), cfg)
    with pytest.raises(ChunkRetryError) as exc:
        plan.run(g)
    assert isinstance(exc.value.__cause__, DeviceLostError)


def test_pallas_compile_failure_demotes_to_xla():
    g = _graph()
    want = brute_force_census(g).counts
    cfg = EngineConfig(backend="pallas", batch=16, chunk_dyads=64,
                       fault_plan=FaultPlan(compile_failure=("pallas",)))
    with pytest.warns(RuntimeWarning,
                      match="pallas backend demoted to xla at compile"):
        plan = compile(g, ("triad_census",), cfg)
    assert plan.requested_backend == "pallas"
    assert plan.backend == "xla"  # demoted at build time
    assert plan.degradation and plan.degradation[0]["rung"] == "pallas->xla"
    assert plan.degradation[0]["stage"] == "compile"
    res = plan.run(g)
    assert np.array_equal(res["triad_census"].counts, want)
    assert plan.stats["faults"]["backend_fallbacks"] == 1
    # the ladder is introspectable from the cache, not just the plan
    entry = [e for e in plan_cache_stats()["entries"]
             if e["requested_backend"] == "pallas"]
    assert entry and entry[0]["degradation"][0]["stage"] == "compile"


def test_pallas_runtime_failure_demotes_to_xla():
    g = _graph()
    want = brute_force_census(g).counts
    cfg = EngineConfig(backend="pallas", batch=16, chunk_dyads=64,
                       fault_plan=FaultPlan(runtime_failure=("pallas",)))
    plan = compile(g, ("triad_census",), cfg)
    assert plan.backend == "pallas"  # compiles fine, fails at dispatch
    with pytest.warns(RuntimeWarning,
                      match="pallas backend demoted to xla at runtime"):
        res = plan.run(g)
    assert plan.backend == "xla"
    assert np.array_equal(res["triad_census"].counts, want)
    assert plan.degradation[0]["stage"] == "runtime"
    # the demoted plan keeps serving (no re-demotion, stable results)
    res2 = plan.run(g)
    assert np.array_equal(res2["triad_census"].counts, want)
    assert plan.stats["faults"]["backend_fallbacks"] == 1


def test_backend_fallback_disabled_reraises():
    g = _graph()
    cfg = EngineConfig(backend="pallas", batch=16, chunk_dyads=64,
                       backend_fallback=False,
                       fault_plan=FaultPlan(compile_failure=("pallas",)))
    with pytest.raises(InjectedFault):
        compile(g, ("triad_census",), cfg)


def test_faulty_and_clean_configs_never_share_plans():
    g = _graph()
    faulty = compile(g, ("triad_census",),
                     EngineConfig(backend="xla", fault_plan=CHAOS))
    clean = compile(g, ("triad_census",),
                    EngineConfig(backend="xla", fault_plan=CLEAN))
    assert faulty is not clean
    assert len(plan_cache_stats()["entries"]) == 2


def test_raise_worker_errors_attaches_secondaries():
    e1, e2, e3 = RuntimeError("a"), RuntimeError("b"), RuntimeError("c")
    with pytest.raises(RuntimeError, match="a") as exc:
        _raise_worker_errors([e1, e2, e3])
    assert isinstance(exc.value.__cause__, WorkerFailures)
    assert exc.value.__cause__.errors == [e2, e3]  # nothing dropped
    solo = RuntimeError("solo")
    with pytest.raises(RuntimeError, match="solo") as exc:
        _raise_worker_errors([solo])
    assert exc.value.__cause__ is None  # single failure stays plain


# ----------------------------------------------------------------------------
# serve-layer hardening: isolation, admission, deadlines, rollback
# ----------------------------------------------------------------------------

def _svc_cfg(**kw):
    census = kw.pop("census", EngineConfig(backend="xla", fault_plan=CLEAN))
    return ServiceConfig(census=census, **kw)


def test_poison_graph_fails_alone_peers_complete():
    g1, bad, g3 = (generators.rmat(6, edge_factor=4, seed=s)
                   for s in (1, 2, 3))
    svc = CensusService(_svc_cfg(max_batch=8))
    poison(bad)
    try:
        rids = [svc.submit(g) for g in (g1, bad, g3)]
        comps = {c.request_id: c for c in svc.flush()}
    finally:
        unpoison(bad)
    assert isinstance(comps[rids[1]].error, InjectedFault)
    assert comps[rids[1]].result is None
    for rid, g in ((rids[0], g1), (rids[2], g3)):
        assert comps[rid].error is None
        assert np.array_equal(comps[rid].result.counts,
                              brute_force_census(g).counts)
    health = svc.stats()["health"]
    assert health["poisoned"] == 1
    assert health["batch_failures"] == 1  # the vmapped unit retried member-wise
    assert svc.pending == 0


def test_admission_reject_policy():
    g = _graph()
    svc = CensusService(_svc_cfg(max_batch=8, max_pending=2))
    svc.submit(g)
    svc.submit(g)
    with pytest.raises(AdmissionError):
        svc.submit(g)
    assert svc.stats()["health"]["rejections"] == 1
    assert svc.pending == 2  # the rejected request took no state
    svc.flush()


def test_admission_flush_oldest_policy():
    g = _graph()
    svc = CensusService(_svc_cfg(max_batch=8, max_pending=2,
                                 reject_policy="flush_oldest"))
    rids = [svc.submit(g) for _ in range(4)]  # each overflow flushes
    assert svc.pending <= 2
    comps = {c.request_id for c in svc.flush()}
    assert comps == set(rids)  # every admitted request completed


def test_deadline_rounds_expire_clocklessly():
    small, big = _graph(), generators.rmat(9, edge_factor=4, seed=5)
    svc = CensusService(_svc_cfg(max_batch=8))
    with pytest.raises(ValueError, match="deadline_rounds"):
        svc.submit(small, deadline_rounds=-1)
    doomed = svc.submit(small, deadline_rounds=0)
    svc.submit(big)  # a different bucket: its flush advances the round
    big_key = next(k for k in list(svc._pending)
                   if svc._pending[k][0].rid != doomed)
    svc._flush_group(big_key)
    comps = {c.request_id: c for c in svc.flush()}
    assert isinstance(comps[doomed].error, DeadlineExceeded)
    assert comps[doomed].result is None
    st = svc.stats()
    assert st["health"]["expired"] == 1
    assert st["rounds"] >= 1
    assert svc.pending == 0


def test_mutate_failure_rolls_session_back():
    g = _graph()
    fp = FaultPlan(mutate_failure_calls=(1,))  # second application dies
    svc = CensusService(_svc_cfg(
        census=EngineConfig(backend="xla", fault_plan=fp)))
    sid = svc.subscribe(g)
    d = GraphDelta(edges_added=np.array([[0, 1], [2, 3], [4, 5]]))
    svc.mutate(sid, d)  # application #0 succeeds
    want = svc.poll(sid).counts
    d2 = GraphDelta(edges_added=np.array([[6, 7]]))
    with pytest.raises(InjectedFault):
        svc.mutate(sid, d2)  # application #1: injected mid-mutate failure
    # the session served its pre-failure state — graph, raw bins, counts
    assert np.array_equal(svc.poll(sid).counts, want)
    st = svc.stats()
    assert st["sessions"][sid]["failed"] == 1
    assert st["health"]["mutate_failures"] == 1
    # the failed ordinal is consumed: the retry proceeds and commits
    svc.mutate(sid, d2)
    assert svc.stats()["sessions"][sid]["mutations"] == 2


def test_dynamic_flush_records_dead_group_explicitly():
    # satellite regression: a group whose flush thread dies must fail its
    # requests explicitly — error completions, pending drained — while
    # peer groups' results are recorded normally.
    small, big = _graph(), generators.rmat(9, edge_factor=4, seed=5)
    svc = CensusService(_svc_cfg(
        max_batch=8,
        census=EngineConfig(backend="xla", schedule="dynamic",
                            fault_plan=CLEAN)))
    ok = svc.submit(small)
    doomed = svc.submit(big)
    doomed_key = next(k for k in list(svc._pending)
                      if svc._pending[k][0].rid == doomed)
    real = svc._execute_group

    def sabotaged(plan, group, _real=real, _key=doomed_key):
        if group[0].rid == doomed:
            raise RuntimeError("group thread died mid-flush")
        return _real(plan, group)

    svc._execute_group = sabotaged
    comps = {c.request_id: c for c in svc.flush()}
    assert svc.pending == 0  # nothing stuck in pending, ever
    assert comps[ok].error is None
    assert np.array_equal(comps[ok].result.counts,
                          brute_force_census(small).counts)
    assert isinstance(comps[doomed].error, RuntimeError)
    assert svc.stats()["health"]["group_failures"] == 1


def test_service_stats_expose_health_and_fallbacks():
    g = _graph()
    svc = CensusService(_svc_cfg(
        census=EngineConfig(backend="xla", chunk_dyads=64, batch=16,
                            fault_plan=CHAOS)))
    rid = svc.submit(g)
    comps = {c.request_id: c for c in svc.flush()}
    assert comps[rid].error is None  # chaos is recoverable, request served
    health = svc.stats()["health"]
    assert set(health) >= {"retries", "quarantines", "backend_fallbacks",
                           "schedule_fallbacks", "rejections", "poisoned",
                           "expired", "batch_failures", "group_failures",
                           "mutate_failures"}
    assert health["retries"] > 0  # engine recoveries aggregate upward
    assert health["poisoned"] == 0


# ----------------------------------------------------------------------------
# environment hook + the real multi-device pool (subprocesses)
# ----------------------------------------------------------------------------

def test_env_fault_plan_governs_default_configs():
    code = """
import numpy as np
from repro.core import brute_force_census, generators
from repro.engine import EngineConfig, FaultPlan, compile, fault_plan_from_env
plan_env = fault_plan_from_env()
assert plan_env is not None and plan_env.seed == 3
g = generators.rmat(7, edge_factor=4, seed=11)
want = brute_force_census(g).counts
# default config (fault_plan=None) inherits the environment chaos...
chaos = compile(g, ("triad_census",),
                EngineConfig(backend="xla", batch=16, chunk_dyads=64))
assert np.array_equal(chaos.run(g)["triad_census"].counts, want)
assert chaos.stats["faults"]["retries"] > 0
# ...and an explicitly inert plan opts out, even under the env hook.
quiet = compile(g, ("triad_census",),
                EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                             fault_plan=FaultPlan()))
assert np.array_equal(quiet.run(g)["triad_census"].counts, want)
assert quiet.stats["faults"]["chunk_failures"] == 0
print('OK')
"""
    env = {**os.environ, "PYTHONPATH": SRC,
           "REPRO_FAULT_PLAN":
               '{"seed": 3, "chunk_failure_rate": 0.5, "fail_attempts": 1}'}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_env_fault_plan_rejects_malformed_json():
    code = """
from repro.engine import fault_plan_from_env
try:
    fault_plan_from_env()
except ValueError as e:
    assert 'REPRO_FAULT_PLAN' in str(e)
    print('OK')
"""
    env = {**os.environ, "PYTHONPATH": SRC,
           "REPRO_FAULT_PLAN": '{"no_such_knob": 1}'}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_device_loss_quarantine_on_real_pool():
    # forced 8 host devices (the flag must precede jax init): lose one
    # device mid-run AND sprinkle recoverable chunk failures — the
    # survivors absorb the re-queued work, the result stays bit-identical
    # in one sync, and the loss/quarantine land in the fault counters.
    code = """
import numpy as np, jax
assert len(jax.devices()) == 8
from repro.core import brute_force_census, generators
from repro.engine import EngineConfig, FaultPlan, compile
g = generators.rmat(8, edge_factor=6, seed=11)
want = brute_force_census(g).counts
# device 2 is dead on arrival: it can never fold a chunk, so the moment
# its worker pulls a task the loss + quarantine fire.  Whether that
# worker wins a task at all is a thread race against the queue draining,
# so run the (cheap, warm) plan a few times — each run re-races — and
# require the loss to land within the budget.
plan = compile(g, ("triad_census",),
               EngineConfig(backend="xla", batch=16, chunk_dyads=32,
                            schedule="dynamic",
                            fault_plan=FaultPlan(seed=3,
                                                 chunk_failure_rate=0.2,
                                                 fail_attempts=1,
                                                 device_loss=(2,))))
runs = 0
for _ in range(8):
    res = plan.run(g)
    runs += 1
    assert np.array_equal(res["triad_census"].counts, want)
    if plan.stats["faults"]["device_losses"]:
        break
fs = plan.stats["faults"]
assert fs["device_losses"] >= 1 and fs["quarantines"] >= 1, fs
assert fs["schedule_fallbacks"] == 0, fs  # survivors finished the queue
assert plan.stats["host_syncs"] == runs  # recovery never adds a sync
assert sum(plan.stats["device_chunks"].values()) == plan.stats["chunks"]
assert 2 not in plan.stats["device_chunks"]  # the dead device folded nothing
print('OK')
"""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    env.pop("REPRO_FAULT_PLAN", None)  # the inline plan is the fixture
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


# ----------------------------------------------------------------------------
# concurrent-shard (partitioned) fault paths
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["pool", "serial"])
def test_partitioned_chunk_chaos_recovers_bit_identical(mode):
    # recoverable chunk failures inside a partitioned run: the executor's
    # bounded retry recovers every shard's chunks, the merged result is
    # bit-identical to a clean unpartitioned pass, still ONE sync.
    g = _graph()
    want = brute_force_census(g).counts
    plan = compile(g, ("triad_census",),
                   EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                                partitions=4, partition_mode=mode,
                                fault_plan=CHAOS))
    res = plan.run(g)
    assert np.array_equal(res["triad_census"].counts, want)
    fs = plan.stats["faults"]
    assert fs["chunk_failures"] > 0 and fs["retries"] > 0
    assert plan.stats["host_syncs"] == 1
    ps = plan.stats["partition"]
    assert ps["mode"] == mode
    # staging stays hoisted even under chaos: retries reuse the resident
    # context, they never re-stage it.
    assert ps["h2d_puts"] == sum(1 for d in ps["shard_dyads"] if d)


def test_partitioned_pool_device_loss_falls_back_bit_identical():
    # a 1-wide pool loses its only device mid-shard: the pinned rung
    # re-runs the shard from its seed with loss injection suppressed,
    # re-staging via rebuild() — recovered results stay bit-identical.
    g = _graph()
    want = brute_force_census(g).counts
    plan = compile(g, ("triad_census",),
                   EngineConfig(backend="xla", batch=16, chunk_dyads=64,
                                schedule="dynamic", n_executor_devices=1,
                                partitions=4, partition_mode="pool",
                                fault_plan=FaultPlan(seed=1,
                                                     device_loss=(0,))))
    res = plan.run(g)
    assert np.array_equal(res["triad_census"].counts, want)
    fs = plan.stats["faults"]
    assert fs["device_losses"] >= 1
    assert fs["schedule_fallbacks"] >= 1
    assert plan.stats["host_syncs"] == 1
    assert any(e[0] == "schedule_fallback"
               for e in plan.stats["fault_events"])
