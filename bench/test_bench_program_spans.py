"""The program's spans read beside the device planes: on a trace recorded
on a TPU v5e chip (two censuses of the scale-8 Kronecker graph by a
program that opens ``repro.*`` spans, bench/fixtures) and on made-up
records."""
import gzip
import os
import types

import pytest

from benchlib import program_spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures",
                       "census_rmat8_spans_v5e.xplane.pb.gz")
OLD_FIXTURE = os.path.join(HERE, "fixtures", "census_rmat8_v5e.xplane.pb.gz")
LAYERS = trace.load_layers(os.path.join(HERE, "layers"))
PLANE = "/device:TPU:0"
MAIN = ("/host:CPU", 0)


def _load(path):
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    return program_spans.load(data=data)


@pytest.fixture(scope="module")
def recorded():
    return _load(FIXTURE)


@pytest.fixture(scope="module")
def reduced(recorded):
    return trace.reduce(recorded.base, LAYERS)


@pytest.fixture(scope="module")
def reading(recorded):
    return program_spans.read(recorded, LAYERS)


def made_up(chunks, modules, thread=MAIN):
    """A window [0, 1000) on one chip: ``chunks`` are ``(start, K)`` of
    ``repro.chunk`` spans, ``modules`` ``(start, end, name)`` of module
    events, each with one op."""
    host = [program_spans.Span("bench.window", 0, 1000, {}, MAIN)]
    host += [program_spans.Span("repro.chunk", s, s + 1,
                                {"K": k, "start": 0, "end": 1}, thread)
             for s, k in chunks]
    ops = [trace.Op(s, e - s, "%a = fusion(x)", "jit_pallas_chunk")
           for s, e, _ in modules]
    base = trace.Trace(ops={PLANE: ops}, spans=[("bench.window", 0, 1000)])
    return program_spans.ProgramTrace(base=base, host=host,
                                      modules={PLANE: list(modules)})


def test_pairing_gives_device_seconds_per_tile_width():
    t = made_up([(1, 32), (2, 4096), (3, 32)],
                [(10, 20, "jit_pallas_chunk(1)"),
                 (20, 60, "jit_pallas_chunk(2)"),
                 (60, 65, "jit_pallas_chunk(1)")])
    seconds, by_layer = program_spans.buckets(t, 0, 1000, [PLANE], LAYERS)
    assert seconds == pytest.approx({32: 15e-9, 4096: 40e-9})
    assert by_layer[4096]["tile gather"] == pytest.approx(40e-9)


@pytest.mark.parametrize("chunks,modules,threads", [
    # one module event more than spans
    ([(1, 32)], [(10, 20, "jit_pallas_chunk(1)"),
                 (20, 30, "jit_pallas_chunk(1)")], None),
    # one fingerprint, two tile widths
    ([(1, 32), (2, 128)], [(10, 20, "jit_pallas_chunk(1)"),
                           (20, 30, "jit_pallas_chunk(1)")], None),
    # spans from two threads: their order is not the device's
    ([(1, 32), (2, 32)], [(10, 20, "jit_pallas_chunk(1)"),
                          (20, 30, "jit_pallas_chunk(1)")], 2),
])
def test_pairing_that_does_not_hold_reads_none(chunks, modules, threads):
    t = made_up(chunks, modules)
    if threads:
        t.host[-1].thread = ("/host:CPU", 7)
    assert program_spans.buckets(t, 0, 1000, [PLANE], LAYERS) is None


def test_a_program_without_spans_reads_nothing_and_raises_nothing():
    old = program_spans.read(_load(OLD_FIXTURE), LAYERS)
    assert old.bucket_s is None and old.bucket_layer_s is None
    assert not any(n.startswith("repro.") for n in old.span_s)
    assert {n for n, _ in old.idle_gaps} <= {"bench.run", "bench.from_edges",
                                             program_spans.OUTSIDE}


def test_recorded_trace_loads_program_spans_with_their_args(recorded):
    names = sorted({s.name for s in recorded.host})
    assert names == ["bench.census", "bench.from_edges", "bench.window",
                     "repro.chunk", "repro.compile", "repro.enumerate",
                     "repro.fetch", "repro.finalize", "repro.from_edges",
                     "repro.run", "repro.schedule", "repro.stage",
                     "repro.wait"]
    chunks = [s.args for s in recorded.host if s.name == "repro.chunk"]
    assert [(c["K"], c["start"], c["end"]) for c in chunks] == [
        (32, 0, 307), (128, 307, 1965), (256, 1965, 2131)] * 2
    assert [s.args["run"] for s in recorded.host
            if s.name == "repro.run"] == [2, 3]
    # the benchmark's own records are as trace.load gives them
    assert sorted(n for n, _, _ in recorded.base.spans) == [
        "bench.census", "bench.census", "bench.from_edges",
        "bench.from_edges", "bench.window"]


def test_recorded_trace_reads_pinned_span_seconds(reading):
    s = reading.span_s
    assert s["repro.stage"] == pytest.approx((0.008908849, 2))
    assert s["repro.schedule"] == pytest.approx((0.000833481, 2))
    assert s["repro.chunk"] == pytest.approx((0.00720051, 6))
    assert s["repro.wait"] == pytest.approx((0.009084929, 2))
    assert s["repro.fetch"] == pytest.approx((0.149258416, 2))
    assert s["bench.census"][1] == 2


def test_every_chunk_program_pairs_with_one_span_and_one_width(
        recorded, reduced, reading):
    lo, hi = trace.window_of(recorded.base)
    events = [ev for ev in recorded.modules[PLANE]
              if ev[2].startswith("jit_pallas_chunk(") and lo <= ev[0] < hi]
    assert len(events) == 6 and len({ev[2] for ev in events}) == 3
    assert set(reading.bucket_s) == {32, 128, 256}
    assert reading.bucket_s == pytest.approx(
        {32: 0.012730415, 128: 0.05619825, 256: 0.091686325})
    assert sum(reading.bucket_s.values()) == pytest.approx(
        sum(e - s for s, e, _ in events) * 1e-9)
    # each bucket splits into the gather and the kernel of its programs
    for k, layers in reading.bucket_layer_s.items():
        assert set(layers) == {"tile gather", "census kernel"}
        assert sum(layers.values()) <= reading.bucket_s[k]
    gathered = sum(v["tile gather"] for v in reading.bucket_layer_s.values())
    assert gathered == pytest.approx(reduced.layer_s["tile gather"])


def test_idle_gaps_are_named_by_the_programs_spans(reduced, reading):
    gaps = dict(reading.idle_gaps)
    assert all(name.startswith("repro.") for name in gaps)
    assert sum(gaps.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s)
    assert reading.idle_gaps[0] == ("repro.from_edges",
                                    pytest.approx(0.008965596))


def test_pad_ratio_reads_the_programs_plans_when_the_loop_has_no_slots():
    """On the CPU (pallas in interpret mode): with only the necessary
    bytes in the counters, the reader takes the plans' own tile slots
    per census run."""
    from benchlib import cells, generators, peaks
    from repro.core.graph import from_edges
    from repro.engine import EngineConfig, clear_plan_cache, compile
    reader = cells.load_module("metrics", "gather_pad_ratio.census")
    n, src, dst = generators.kronecker(6, 4, seed=0)
    config = EngineConfig(backend="pallas", chunk_dyads=64, buckets=(4, 8))
    clear_plan_cache()
    try:
        for _ in range(2):
            g = from_edges(n, src, dst)
            plan = compile(g, ["triad_census"], config)
            plan.run(g)
        need = peaks.census_bytes(n, src, dst)
        ctx = types.SimpleNamespace(counters={"necessary_bytes": need},
                                    work=1)
        assert plan.stats["runs"] == 2 and plan.stats["tile_slots"] > 0
        assert reader.read(ctx) == pytest.approx(
            4.0 * plan.stats["tile_slots"] / 2 / need)
    finally:
        clear_plan_cache()
    assert reader.read(ctx) is None


def test_record_trace_reports_per_census(recorded, reading):
    import record_trace
    out = record_trace.report(recorded, LAYERS, censuses=2)
    assert out["span_ms"]["repro.chunk"] == pytest.approx(
        [1e3 * reading.span_s["repro.chunk"][0] / 2, 6])
    assert set(out["bucket_ms"]) == {32, 128, 256}
    assert out["bucket_ms"][256]["device_ms"] == pytest.approx(
        1e3 * reading.bucket_s[256] / 2)
    assert out["idle_ms"]["repro.from_edges"] == pytest.approx(
        1e3 * 0.008965596 / 2)
