"""Every cell, configuration and metric is found by its name."""
import importlib
import json

import pytest

from chipbench import spec

BENCH = spec.load_json(spec.BENCHMARK)
CELLS = sorted(p.stem for p in spec.WORKLOADS.glob("*.json"))


@pytest.mark.parametrize("name", CELLS)
def test_workload_resolves_its_configuration(name):
    cell = spec.workload(name)
    assert cell["model"]["name"] == cell["config"]
    assert cell["rounds_per_run"] % cell["eval_every"] == 0
    assert len(cell["clusters"]) == len(cell["transforms"])
    assert cell["chips"] in (1, 4)
    if cell["mesh"]:
        assert sum(cell["clusters"]) % cell["mesh"][0] == 0
    cfg = spec.cnn_config(cell["model"])
    assert cfg.dtype == "float32"


def test_benchmark_entries_have_their_files():
    for c in BENCH["configs"]:
        assert spec.config(c["name"])["name"] == c["name"]
        assert (spec.ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        cell = spec.workload(w["traffic"])
        assert cell["config"] == w["config"]
        assert cell["chips"] == w["chips"]
        assert set(cell["limits"]) >= {"model_gap", "select_miss",
                                       "pred_gap", "bytes_gap"}
        assert cell["select_margin"] > 0
    for m in BENCH["per_layer"]:
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        assert callable(reader.read)
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in BENCH["workloads"]}


def test_benchmark_is_small_and_well_formed():
    text = spec.BENCHMARK.read_text()
    assert len(text.encode()) < 64 * 1024
    assert set(json.loads(text)) == {"command", "paths", "run_seconds",
                                     "configs", "workloads", "end_to_end",
                                     "per_layer"}
