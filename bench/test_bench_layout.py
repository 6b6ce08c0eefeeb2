"""Everything BENCHMARK.json names is found by its name, and each
per-layer reader reads its own example, or nothing.  Nothing here lists
cells, metrics or layers: a new one is checked by its files alone."""
import json
import os
import types

import pytest

from benchlib import cells, peaks, trace

BENCH = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
LAYERS = trace.load_layers(os.path.join(cells.BENCH, "layers"))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_files(w):
    cell = cells.load_cell(w["name"])
    cells.load_module("loops", cell.traffic["loop"])
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def make_ctx(trace_fields=None, work=0, spans=None, counters=None):
    """What a reader is given, from a reader's ``EXAMPLE["ctx"]``."""
    red = None
    if trace_fields is not None:
        red = trace.Reduction(**{"window_s": 1.0, "busy_s": 0.0,
                                 "devices": 1, "layer_s": {}, "top_ops": [],
                                 "idle_gaps": [], **trace_fields})
    return types.SimpleNamespace(trace=red, work=work, spans=spans or {},
                                 counters=counters or {},
                                 peaks=peaks.peaks("TPU v5 lite"))


def _reader_ctx(example):
    ctx = dict(example)
    return make_ctx(ctx.pop("trace", None), **ctx)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_reader_reads_its_example_or_nothing(m):
    reader = cells.load_module("metrics", m["name"])
    assert reader.read(_reader_ctx(reader.EXAMPLE["ctx"])) == pytest.approx(
        reader.EXAMPLE["value"])
    assert reader.read(make_ctx()) is None


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_layer_a_reader_names_has_an_op_file(m):
    reader = cells.load_module("metrics", m["name"])
    named = list(getattr(reader, "LAYERS", ()))
    if hasattr(reader, "LAYER"):
        named.append(reader.LAYER)
    for layer in named:
        assert LAYERS.get(layer), f"no bench/layers/*.json for {layer!r}"
