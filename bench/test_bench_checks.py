"""The check that decides ``correct``, driven end to end on the CPU at a
size a test run holds: sound runs pass; the control (the reference
without its union test, in the program's place) fails; and each fault
the cells can have, planted in the program under the timed path, fails.

The harness's look for a chip is skipped: the runs call
``harness.run_cell`` directly, where the program resolves ``auto`` to
its xla backend.
"""
import functools
import json
import os
import time

import pytest

from benchlib import cells, harness, reference

SMALL = {
    "graph500_s13.census": {"graph": {"kind": "kronecker", "scale": 7,
                                      "edge_factor": 8, "seed": 0}},
}
SECONDS = {"graph500_s13.census": 0.5}
END_TO_END = {"census": ["census_s"]}


def small_cell(name):
    """The cell's own configuration and traffic files, on a small graph."""
    config, traffic = name.split(".")

    def load(*path):
        with open(os.path.join(cells.BENCH, *path)) as f:
            return json.load(f)
    return cells.Cell(
        name=name, chips=1,
        config={**load("configs", config + ".json"),
                "graph": SMALL[name]["graph"]},
        traffic={**load("traffic", traffic + ".json"),
                 **SMALL[name].get("traffic", {})},
        end_to_end=[{"name": m, "unit": "-"}
                    for m in END_TO_END[traffic] + ["setup_s"]],
        per_layer=[])


def run(name, seed=2 ** 31 + 99, control=None):
    from repro.engine import clear_plan_cache
    clear_plan_cache()
    return harness.run_cell(small_cell(name), seed, SECONDS[name], False,
                            t_start=time.perf_counter(),
                            expected_backend="xla", control=control,
                            compile_cache=False, log=lambda m: None)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct_and_the_control_is_not(name):
    control = functools.partial(reference.triad_census, dedup=False)
    r = run(name, control=control)
    assert r["correct"] is True
    assert r["checks"] == {"wrong_answers": {"value": 0, "limit": 0}}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["control"]["checked"] > 0
    assert r["control"]["wrong_answers"] == r["control"]["checked"]
    assert list(r)[-1] == "checks"


def _altered_answer(monkeypatch):
    """The census op turns its raw bins into counts one count off."""
    from repro.core.census import CensusResult
    from repro.engine.ops import get_op
    op = type(get_op("triad_census"))
    orig = op.finalize

    def finalize_altered(self, raw, g):
        c = orig(self, raw, g).counts.copy()
        c[5] += 1
        return CensusResult(c)
    monkeypatch.setattr(op, "finalize", finalize_altered)


@pytest.mark.parametrize("name,fault", [
    ("graph500_s13.census", _altered_answer),
])
def test_a_fault_under_the_timed_path_is_not_correct(name, fault,
                                                     monkeypatch):
    fault(monkeypatch)
    r = run(name)
    assert r["correct"] is False
    assert r["checks"]["wrong_answers"]["value"] > 0
