#!/usr/bin/env python3
"""Smoke run of the triad census on a TPU, through the public entry points.

    python chip_smoke.py               # one chip: phases a, b, c
    python chip_smoke.py --four-chips  # four chips: multi-device paths only

One chip:
  (a) full-size census of the eatSR-shaped graph (n = 32,768, 301,071
      arcs, tile widths up to 8,192) on ``backend="auto"``, which must
      resolve to the compiled pallas kernel and stay there.  Its counts
      must satisfy the dyad-census identities (every mutual and every
      asymmetric dyad lies in n - 2 triads), and on a sample of 8,192 of
      the graph's dyads, stratified over the four tile widths, the
      pallas and xla backends must give the same bins bit for bit.  The
      xla backend probes all 8,192 lanes for every dyad: a whole-graph
      xla run of this graph takes about 2,800 s on a v5e, longer than
      this script may run;
  (b) ``rmat(8)`` on ``backend="auto"`` equals ``brute_force_census``;
  (c) a ``CensusService`` answers 8 ``rmat(10)`` requests in one
      ``flush()``, each equal to ``Plan.run`` on the same graph.

Four chips (``--four-chips``): the one-chip pallas count of the eatSR
graph; the ``partitions=4`` pool-mode census of the same graph, which
must equal it with every device doing work; and ``backend="distributed"``
over a 4-device mesh on the phase (a) dyad sample (its kernel is the
xla one), which must equal the one-chip pallas bins of that sample.

Times printed here are smoke numbers (host clock, one run each), not
benchmark metrics.  Any fallback — a demoted plan, a quarantined device,
the Pallas interpreter, no TPU at all — is a failure: the script exits
non-zero and prints no result line.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or degraded result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def phase(label: str, /, **fields) -> None:
    print(json.dumps({"phase": label, **fields}), flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation, from its own events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def check_not_degraded(plan, label: str) -> None:
    """The no-fallback contract: the compiled pallas kernel ran, nothing
    was demoted or quarantined, and the interpreter was not used."""
    check(plan.backend == "pallas", f"{label}: plan runs on {plan.backend!r}, "
          "not pallas")
    check(not plan.degradation, f"{label}: plan degraded: {plan.degradation}")
    check(plan.stats["faults"]["quarantines"] == 0,
          f"{label}: {plan.stats['faults']['quarantines']} device(s) "
          "quarantined")
    check(plan.config.resolve_interpret() is False,
          f"{label}: pallas would run in interpret mode")


def timed_run(plan, g, clock: CompileClock):
    """One run: host wall seconds and compile seconds inside it.  A run
    ends in the device-to-host fetch of the raw bins, so the window holds
    every device operation the run issued."""
    c0, t0 = clock.seconds, time.perf_counter()
    res = plan.run(g)
    counts = res["triad_census"].counts
    return counts, time.perf_counter() - t0, clock.seconds - c0


def eatsr_graph():
    from repro.core import generators
    g = generators.paper_profile("eatSR", scale_down=1)
    phase("graph", name="eatSR", n=g.n, arcs=g.m, dyads=g.n_dyads,
          max_deg=g.max_deg)
    return g


def dyad_identities(g, counts) -> None:
    """Every mutual (M) and asymmetric (A) dyad lies in n - 2 triads, so
    sum_t M_t * count_t = M (n - 2), and likewise for A — an exact check
    of the 16 counts against dyad counts taken straight from the arcs."""
    import numpy as np
    from repro.core.triad_table import TRIAD_NAMES
    out_ptr = np.asarray(g.arrays.out_ptr, dtype=np.int64)
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(out_ptr))
    dst = np.asarray(g.arrays.out_idx, dtype=np.int64)[: len(src)]
    mutual = int(np.isin(src * g.n + dst, dst * g.n + src).sum()) // 2
    asym = len(src) - 2 * mutual
    m_t = np.array([int(name[0]) for name in TRIAD_NAMES])
    a_t = np.array([int(name[1]) for name in TRIAD_NAMES])
    check(int(m_t @ counts) == mutual * (g.n - 2),
          "a: counts break the mutual-dyad identity")
    check(int(a_t @ counts) == asym * (g.n - 2),
          "a: counts break the asymmetric-dyad identity")


def dyad_sample(g, ks, per_bucket: int, heaviest: int):
    """Canonical dyads of ``g``: the ``heaviest`` by tile-width need plus
    ``per_bucket`` drawn (seeded) from each tile-width bucket."""
    import numpy as np
    from repro.core.census import canonical_dyads
    u, v = canonical_dyads(g)
    deg = np.asarray(g.arrays.nbr_deg)
    out_deg = np.diff(np.asarray(g.arrays.out_ptr))
    need = np.maximum(np.maximum(deg[u], deg[v]),
                      np.maximum(out_deg[u], out_deg[v]))
    bucket = np.searchsorted(np.asarray(ks), need)
    top = np.argsort(-need, kind="stable")[:heaviest]
    rest = np.setdiff1d(np.arange(len(u)), top)
    rng = np.random.default_rng(0)
    pick = [top] + [rng.choice(rest[bucket[rest] == b], per_bucket,
                               replace=False) for b in range(len(ks))]
    sel = np.sort(np.concatenate(pick))
    return u[sel], v[sel]


def sample_bins(plan, g, u, v):
    """Raw census bins of ``g`` summed over the dyads ``(u, v)`` only: the
    engine's affected-subset pass, asked for the change from an empty
    dyad set on the same graph."""
    from repro.engine import GraphDelta, delta_correction
    return delta_correction(plan, g, g, GraphDelta(), affected_old=(u[:0],
                            v[:0]), affected_new=(u, v))


def phase_full_census(clock: CompileClock) -> None:
    from repro.engine import EngineConfig, compile
    from repro.engine.plan import GraphMeta
    g = eatsr_graph()
    plan = compile(g, ["triad_census"], EngineConfig(backend="auto"))
    check_not_degraded(plan, "a")
    counts, cold_s, compile_s = timed_run(plan, g, clock)
    check_not_degraded(plan, "a")
    _, warm_s, warm_compile_s = timed_run(plan, g, clock)
    check_not_degraded(plan, "a")
    check(warm_compile_s == 0.0, f"a: warm run compiled for {warm_compile_s}s")
    check(int(counts.sum()) == math.comb(g.n, 3), "a: counts do not sum to "
          "C(n, 3)")
    dyad_identities(g, counts)
    phase("a_pallas", backend=plan.backend,
          tile_width=GraphMeta.from_graph(g).k, degradation=plan.degradation,
          counts=counts.tolist(), dyad_identities=True, cold_s=cold_s,
          compile_s=compile_s, warm_s=warm_s, chunks=plan.stats["chunks"],
          host_syncs=plan.stats["host_syncs"])
    ks = (32, 128, 512, GraphMeta.from_graph(g).k)
    u, v = dyad_sample(g, ks, per_bucket=1984, heaviest=256)
    got = sample_bins(plan, g, u, v)
    check_not_degraded(plan, "a")
    xla = compile(g, ["triad_census"], EngineConfig(backend="xla"))
    t0, c0 = time.perf_counter(), clock.seconds
    want = sample_bins(xla, g, u, v)
    xla_s, xla_compile_s = time.perf_counter() - t0, clock.seconds - c0
    check(xla.backend == "xla" and not xla.degradation, "a: xla plan degraded")
    check(got.tolist() == want.tolist(),
          f"a: pallas {got.tolist()} != xla {want.tolist()} on the sample")
    phase("a_xla_sample", dyads=len(u), equals_pallas=True, xla_s=xla_s,
          xla_compile_s=xla_compile_s)


def phase_oracle() -> None:
    from repro.core import brute_force_census, generators
    from repro.engine import EngineConfig, compile
    g = generators.rmat(8)
    plan = compile(g, ["triad_census"], EngineConfig(backend="auto"))
    got = plan.run(g)["triad_census"].counts
    check_not_degraded(plan, "b")
    want = brute_force_census(g).counts
    check(got.tolist() == want.tolist(),
          f"b: pallas {got.tolist()} != brute force {want.tolist()}")
    phase("b_oracle", n=g.n, arcs=g.m, equals_brute_force=True)


def phase_service() -> None:
    from repro.core import generators
    from repro.engine import EngineConfig, compile
    from repro.serve import CensusService, ServiceConfig
    graphs = [generators.rmat(10, seed=s) for s in range(8)]
    svc = CensusService(ServiceConfig(max_batch=16,
                                      census=EngineConfig(backend="auto")))
    rids = [svc.submit(g) for g in graphs]
    check(svc.pending == 8, f"c: {8 - svc.pending} request(s) ran before "
          "flush()")
    t0 = time.perf_counter()
    done = {c.request_id: c for c in svc.flush()}
    flush_s = time.perf_counter() - t0
    check(sorted(done) == sorted(rids), "c: flush() did not answer all 8")
    for rid, g in zip(rids, graphs):
        c = done[rid]
        check(c.error is None, f"c: request {rid} failed: {c.error!r}")
        plan = compile(g, ["triad_census"], svc.config.census)
        check_not_degraded(plan, "c")
        want = plan.run(g)["triad_census"].counts
        check(c.result.counts.tolist() == want.tolist(),
              f"c: request {rid} differs from Plan.run")
    health = svc.stats()["health"]
    check(health["quarantines"] == 0 and health["backend_fallbacks"] == 0,
          f"c: service health {health}")
    phase("c_service", requests=len(rids), flush_s=flush_s,
          equals_plan_run=True)


def phase_four_chips(clock: CompileClock) -> None:
    import jax
    import numpy as np
    from repro.engine import EngineConfig, compile
    from repro.engine.plan import GraphMeta
    g = eatsr_graph()
    one = compile(g, ["triad_census"], EngineConfig(backend="auto"))
    want, one_s, _ = timed_run(one, g, clock)
    check_not_degraded(one, "one chip")
    dyad_identities(g, want)
    ks = (32, 128, 512, GraphMeta.from_graph(g).k)
    u, v = dyad_sample(g, ks, per_bucket=1984, heaviest=256)
    want_sample = sample_bins(one, g, u, v)
    check_not_degraded(one, "one chip")
    phase("one_chip", counts=want.tolist(), run_s=one_s)

    # the dynamic schedule is what gives the executor a pool of all four
    # devices; the static schedule pins every shard to one.
    part = compile(g, ["triad_census"],
                   EngineConfig(partitions=4, schedule="dynamic"))
    got, part_s, _ = timed_run(part, g, clock)
    check_not_degraded(part, "partitions=4")
    check(got.tolist() == want.tolist(),
          f"partitions=4 {got.tolist()} != one chip {want.tolist()}")
    chunks = part.stats["device_chunks"]
    pstats = part.stats["partition"]
    homes = {t["device"] for t in pstats["shard_times"].values()}
    check(len(chunks) == 4 and all(c > 0 for c in chunks.values()),
          f"partitions=4: chunks per device {chunks}")
    check(len(homes) == 4, f"partitions=4: shards ran on devices {homes}")
    phase("partitions_4", device_chunks={str(k): v for k, v in
                                         sorted(chunks.items())},
          mode=pstats["mode"], shard_dyads=pstats["shard_dyads"],
          shard_devices=sorted(homes), equals_one_chip=True, run_s=part_s)

    mesh = jax.make_mesh((4,), ("data",))
    dist = compile(g, ["triad_census"], EngineConfig(backend="distributed"),
                   mesh=mesh)
    t0 = time.perf_counter()
    got = sample_bins(dist, g, u, v)
    dist_s = time.perf_counter() - t0
    check(dist.backend == "distributed" and not dist.degradation,
          "distributed: plan degraded")
    check(got.tolist() == want_sample.tolist(),
          f"distributed {got.tolist()} != one chip {want_sample.tolist()} "
          "on the sample")
    # the subset pass deals the sample round-robin over the mesh devices
    per_device = np.bincount(np.arange(len(u)) % 4, minlength=4)
    check(math.prod(dist.mesh.devices.shape) == 4 and (per_device > 0).all(),
          f"distributed: dyads per device {per_device.tolist()}")
    phase("distributed_sample", mesh_devices=4,
          dyads_per_device=per_device.tolist(), equals_one_chip=True,
          run_s=dist_s)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (distributed mesh, "
                         "partitions=4) against the one-chip count")
    args = ap.parse_args()
    try:
        import jax
        from repro.engine.config import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the census engine: {e}",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    want = 4 if args.four_chips else 1
    if platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU device(s), JAX sees "
              f"{len(devices)} {platform!r} device(s)", file=sys.stderr)
        return 1
    phase("start", device_kind=devices[0].device_kind, devices=len(devices),
          jax=jax.__version__, compile_cache=cache)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(clock)
        else:
            phase_full_census(clock)
            phase_oracle()
            phase_service()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    phase("done", total_s=time.perf_counter() - t0,
          compile_s=clock.seconds, compiles=clock.count)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
