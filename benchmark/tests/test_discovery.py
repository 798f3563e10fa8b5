"""A cell, a traffic mix and a per-layer metric are found by name: adding
them as new files and entries needs no edit of any file already there."""

import json
import os
import shutil

import harness


def test_new_cell_and_metric_are_listed_without_edits(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    # new files only: a traffic mix and a metric reader
    with open(os.path.join(root, "benchmark", "traffic", "dummy.mix.json"),
              "w") as f:
        json.dump({"driver": "library_solve", "operands": 1,
                   "trace_solves": 1}, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "dummy.metric.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    # new entries in BENCHMARK.json
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "dummy.cell", "config": "hpl",
                               "traffic": "dummy.mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("dummy.cell")
    bench["per_layer"].append({
        "name": "dummy.metric", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device (XLA:TPU)",
        "moves": "solve_s", "workloads": ["dummy.cell"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    parts = harness.parts(root)
    assert "dummy.cell" in parts["cells"]
    assert parts["cells"]["dummy.cell"]["per_layer"] == ["dummy.metric"]
    assert "solve_s" in parts["cells"]["dummy.cell"]["end_to_end"]
    assert "dummy.metric" in parts["metrics"]
    # every file that was there is byte-identical
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_every_named_part_resolves():
    parts = harness.parts()
    bench = harness.benchmark()
    assert set(parts["cells"]) == {w["name"] for w in bench["workloads"]}
    for name, c in parts["cells"].items():
        assert "setup_s" in c["end_to_end"] and len(c["end_to_end"]) >= 2
        assert c["per_layer"], name
