"""Every cell, end to end on the CPU at a small size: the contract line,
and a correct verdict from the comparison with the reference."""

import pytest

from bench.tests.common import CELLS, SPEC, run_small

pytestmark = pytest.mark.filterwarnings("ignore")


def _names(kind, cell):
    return {m["name"] for m in SPEC[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_prints_the_contract_line(cell):
    r = run_small(cell)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    assert set(r["metrics"]) == _names("end_to_end", cell)
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in r["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    assert r["checks"]["mismatches"] == {"value": 0, "limit": 0}


def test_traced_run_adds_busy_window_and_breakdown():
    cell = "events-1m.dense-batch"
    r = run_small(cell, trace=True)
    assert r["correct"] is True
    assert r["device"]["window_s"] > 0 and "busy_s" in r["device"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no accelerator plane: no device-trace metric may appear
    assert not {m for m in r["metrics"]} & {
        m["name"] for m in SPEC["per_layer"] if m["source"] == "device_trace"}
