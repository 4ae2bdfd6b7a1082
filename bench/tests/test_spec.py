"""Cells, configurations, mixes and metric readers are found by name, so
a new one is new files plus new ``BENCHMARK.json`` entries."""

import json
import os
import shutil

import pytest

from bench import spec
from bench.tests.common import ROOT, SPEC, run_small

pytestmark = pytest.mark.filterwarnings("ignore")


def test_every_entry_resolves_by_name():
    bench = spec.Benchmark(ROOT)
    for c in SPEC["configs"]:
        cfg = bench.config(c["name"])
        assert spec.generator(cfg["generator"]).generate
        assert all(k in cfg for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert bench.workload(w["name"])["config"] in {
            c["name"] for c in SPEC["configs"]}
        assert bench.traffic(w["traffic"])["loop"]["kind"] in ("open", "closed")
        assert bench.metrics(w["name"], "per_layer")
        assert "setup_s" in {m["name"] for m in
                             bench.metrics(w["name"], "end_to_end")}
    for m in SPEC["per_layer"]:
        assert callable(bench.metric_reader(m["name"]))
    with pytest.raises(KeyError):
        bench.workload("no-such-cell")


def test_a_throwaway_cell_needs_only_new_files(tmp_path):
    """A new generator family, configuration, mix and metric, added as
    files beside copies of the existing ones, with entries appended to a
    copy of BENCHMARK.json: the harness runs the new cell untouched."""
    import bench.tables
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "tables" / "throwaway_family.py").write_text(
        "from bench.tables import events\n\n\n"
        "def generate(cfg, rng):\n"
        "    return {'events': events.events(rng, cfg['parts'], 8, 0.999, 0.999),\n"
        "            'users': events.users(rng, 2000, 100)}\n")
    (root / "bench" / "configs" / "throwaway.json").write_text(json.dumps(
        dict(generator="throwaway_family", parts=1024, verdict_cache=False)))
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      "dense-batch.json")))
    mix["loop"]["batch"] = 8
    (root / "bench" / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "throwaway_batches.py").write_text(
        "def read(run):\n    return float(len(run.batches)) or None\n")
    s = json.loads(json.dumps(SPEC))
    s["configs"].append(dict(name="throwaway", source="test", reduced=[],
                             file="bench/configs/throwaway.json", why="test"))
    s["workloads"].append(dict(name="throwaway.cell", config="throwaway",
                               traffic="throwaway-mix", chips=1, why="test"))
    s["per_layer"].append(dict(name="throwaway_batches", unit="batches",
                               better="higher", source="program_counter",
                               layer="service", moves="qps",
                               workloads=["throwaway.cell"]))
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    bench.tables.__path__.append(str(root / "bench" / "tables"))
    try:
        r = run_small("throwaway.cell", trace=True, root=str(root),
                      config="throwaway")
    finally:
        bench.tables.__path__.remove(str(root / "bench" / "tables"))
    assert r["correct"] is True
    assert r["metrics"]["throwaway_batches"]["value"] >= 1
    assert r["metrics"]["throwaway_batches"]["unit"] == "batches"
