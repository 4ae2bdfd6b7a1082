"""The plain reference against the program's own host pipeline, and the
control: the reference one precision down must not pass."""

import numpy as np
import pytest

from bench.tests.common import CELLS, CONFIG_OF, ROOT, SEED, SPEC, small_tables

pytestmark = pytest.mark.filterwarnings("ignore")


def _traffic(name):
    from bench import spec
    return spec.Benchmark(ROOT).traffic(name)


def _mix(cell):
    from bench import spec
    return _traffic(spec.Benchmark(ROOT).workload(cell)["traffic"])


# every cell's pair of configuration and mix, and the open-loop mix
PAIRS = sorted({(CONFIG_OF[c], w["traffic"]) for c in CELLS
                for w in SPEC["workloads"] if w["name"] == c}
               | {("events-1m", "mixed-open")})


@pytest.mark.parametrize("config,traffic", PAIRS)
def test_reference_agrees_with_the_host_pipeline(config, traffic):
    from bench import check, program
    from bench.reference import Reference
    from bench.trafficgen import Traffic
    from repro.core.flow import PruningPipeline
    data = small_tables(config)
    tables = {n: program.build_table(td) for n, td in data.items()}
    host = PruningPipeline(filter_mode="host")
    ref = Reference(data)
    queries = Traffic(_traffic(traffic), SEED).queries(48, 0)
    kinds = set()
    for q in queries:
        got = program.outcome(host.run(program.to_query(q, tables)))
        assert check.differences(got, ref.outcome(q), ref, q) == [], q["kind"]
        kinds.add(q["kind"])
    assert kinds == set(_traffic(traffic)["cycle"])


def test_a_wrong_verdict_is_caught():
    from bench import check
    from bench.reference.pruning import FULL, NO, PARTIAL, Reference
    from bench.trafficgen import Traffic
    data = small_tables("events-1m")
    ref = Reference(data)
    q = Traffic(_mix("events-1m.dense-batch"), SEED).queries(1, 0)[0]
    want = ref.outcome(q)
    ids, match = want.scan_sets["events"]
    assert (match == FULL).any() and len(ids) > 1
    flipped = match.copy()
    flipped[np.argmax(match == FULL)] = PARTIAL
    for bad in ((ids, flipped), (ids[1:], match[1:])):
        got = type(want)({"events": bad})
        assert check.differences(got, want, ref, q)
    tv = ref.tv("events", q["scans"]["events"])
    assert set(np.unique(tv)) <= {NO, PARTIAL, FULL}


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_reads_as_mismatches(cell):
    from bench import control, harness
    data, mix, kept = control.control_outcomes(
        ROOT, cell, SEED, batches=2, overrides=__import__(
            "bench.tests.common", fromlist=["SMALL"]).SMALL[CONFIG_OF[cell]])
    ver = harness.verify(data, kept, mix["check"]["sample"], SEED)
    assert ver["checked"] > 0 and ver["mismatches"] > 0
