"""The trace reduction, on a trace recorded on a TPU v5e chip (one
census of the scale-8 Kronecker graph, bench/fixtures) and on made-up
records."""
import gzip
import os

import pytest

from benchlib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "census_rmat8_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with open(FIXTURE, "rb") as f:
        data = ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    return trace.load(data=data)


def test_recorded_trace_loads_device_ops_modules_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    ops = recorded.ops["/device:TPU:0"]
    assert len(ops) == 1045
    assert {o.module for o in ops} >= {"jit_pallas_chunk",
                                       "jit_enumerate_dyads_device"}
    assert sorted(n for n, _, _ in recorded.spans) == [
        "bench.from_edges", "bench.run", "bench.window"]


def test_recorded_trace_reduces_to_pinned_numbers(recorded):
    layers = trace.load_layers(os.path.join(HERE, "layers"))
    r = trace.reduce(recorded, layers)
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.09417849)
    assert r.busy_s == pytest.approx(0.082070316)
    assert r.layer_s["tile gather"] == pytest.approx(0.078304944)
    assert r.layer_s["census kernel"] == pytest.approx(0.001871021)
    assert r.layer_s["tile gather"] + r.layer_s["census kernel"] <= r.busy_s
    assert r.top_ops[0][0].startswith("jit_pallas_chunk %fusion")
    assert [s for _, s in r.top_ops] == sorted((s for _, s in r.top_ops),
                                               reverse=True)
    # every idle nanosecond of the window is in some labelled gap
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s)
    assert r.idle_gaps[0][0] == "bench.run"


def make(ops, spans):
    return trace.Trace(ops={"/device:TPU:0": [
        trace.Op(s, d, name, module) for s, d, name, module in ops]},
        spans=spans)


def test_union_clipping_gaps_and_layers_on_made_up_records():
    t = make([(0, 20, "%a = fusion(x)", "jit_pallas_chunk"),
              (10, 20, "%k = custom-call(x), custom_call_target="
                       "\"tpu_custom_call\"", "jit_pallas_chunk"),
              (50, 10, "%c = copy(x)", "jit_other"),
              (95, 20, "%a = fusion(x)", "jit_pallas_chunk")],
             [("bench.window", 5, 100), ("bench.from_edges", 30, 45),
              ("bench.census", 45, 100)])
    layers = trace.load_layers(os.path.join(HERE, "layers"))
    r = trace.reduce(t, layers)
    ns = 1e-9
    assert r.window_s == pytest.approx(95 * ns)
    # busy: [5, 30) + [50, 60) + [95, 100)
    assert r.busy_s == pytest.approx(40 * ns)
    assert r.idle_share == pytest.approx(55 / 95)
    assert r.layer_s["tile gather"] == pytest.approx(20 * ns)
    assert r.layer_s["census kernel"] == pytest.approx(20 * ns)
    gaps = dict(r.idle_gaps)
    assert gaps["bench.from_edges"] == pytest.approx(20 * ns)   # [30, 50)
    assert gaps["bench.census"] == pytest.approx(35 * ns)       # [60, 95)


def test_no_device_op_in_the_window_is_an_error():
    t = make([(0, 5, "%a = fusion(x)", "m")], [("bench.window", 10, 20)])
    with pytest.raises(ValueError):
        trace.reduce(t, {})


def test_a_used_chip_that_ran_nothing_counts_as_idle(recorded):
    layers = trace.load_layers(os.path.join(HERE, "layers"))
    one = trace.reduce(recorded, layers)
    two = trace.reduce(recorded, layers,
                       devices=["/device:TPU:0", trace.device_plane(1)])
    assert two.devices == 2
    assert two.busy_s == pytest.approx(one.busy_s / 2)
    assert two.idle_share == pytest.approx((1 + one.idle_share) / 2)
    assert sum(s for _, s in two.idle_gaps) == pytest.approx(
        two.window_s - two.busy_s)


def test_planes_of_chips_the_run_does_not_use_are_left_out(recorded):
    layers = trace.load_layers(os.path.join(HERE, "layers"))
    one = trace.reduce(recorded, layers)
    spare = trace.Trace(ops={**recorded.ops, "/device:TPU:1": [
        trace.Op(0.0, 1e18, "%a = fusion(x)", "jit_other")]},
        spans=recorded.spans)
    used = trace.reduce(spare, layers, devices=[trace.device_plane(0)])
    assert used.devices == 1 and used.busy_s == pytest.approx(one.busy_s)
