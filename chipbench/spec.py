"""Cells and configurations, found by name.

A cell is ``workloads/<name>.json``: the configuration it runs, the chips
it needs, its traffic (cluster split, data sizes, run shape) and the
limits its correctness numbers are held to. A configuration is
``configs/<name>.json``: the model's published sizes, its layer list and
its source.
Nothing here names a particular cell, so a later change adds one by
adding files.
"""
from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
WORKLOADS = HERE / "workloads"
CONFIGS = HERE / "configs"
BENCHMARK = ROOT / "BENCHMARK.json"

# configuration keys that become the program's CNNConfig fields
MODEL_KEYS = ("kind", "image_size", "channels", "n_classes", "width",
              "groups", "dtype")


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(name: str, root: pathlib.Path = WORKLOADS,
             configs: pathlib.Path = CONFIGS) -> dict:
    """The cell's parameters, with its configuration under ``"model"``."""
    cell = load_json(root / f"{name}.json")
    cell["name"] = name
    cell["model"] = config(cell["config"], configs)
    return cell


def config(name: str, root: pathlib.Path = CONFIGS) -> dict:
    cfg = load_json(root / f"{name}.json")
    if cfg["name"] != name:
        raise ValueError(f"configs/{name}.json names itself {cfg['name']!r}")
    return cfg


def cnn_config(model: dict):
    """The program's ``CNNConfig`` for a configuration file."""
    from repro.models.base import CNNConfig

    return CNNConfig(name=model["name"],
                     **{k: model[k] for k in MODEL_KEYS})


def per_layer_metrics(cell: str, benchmark: dict) -> list[dict]:
    """The ``per_layer`` entries of ``BENCHMARK.json`` this cell reports:
    those that list it, and those that list no cells at all."""
    return [m for m in benchmark["per_layer"]
            if cell in m.get("workloads", [cell])]

