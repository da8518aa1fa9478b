"""The data-driven layout: everything a cell names is a file of its own,
and the per-metric JSON beside each reader says what BENCHMARK.json says
of the metric itself. Which cells report it is BENCHMARK.json's alone to
say: a later PR's cell that reports a metric edits no file here."""

import json
import os

import pytest

from chipbench import run, traffic

BENCH = run.load_benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    _, cfg = run.find_cell(BENCH, cell["name"])
    assert os.path.exists(os.path.join(
        run.HERE, "systems", cfg["system"] + ".py"))
    assert traffic.load_traffic(cell["traffic"])["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cfg["reduced"] == entry["reduced"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_reader_and_matching_json(metric):
    assert callable(run.metric_reader(metric["name"]))
    with open(os.path.join(run.HERE, "metrics",
                           metric["name"] + ".json")) as f:
        meta = json.load(f)
    assert meta["what"]
    assert "workloads" not in meta
    for key in ("unit", "better", "source", "layer", "moves"):
        assert meta.get(key) == metric.get(key), key
