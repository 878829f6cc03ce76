"""What the algorithm needs, counted from shapes, and the chips' peaks.

FLOPs count a multiply-add as two, over SAME convolutions and dense
layers, walking the configuration's ``layers``; GroupNorm, ReLU, pooling
and the loss are left out. Per node and
round FACADE needs:

* local SGD: H x B samples x (forward + backward), the backward counted
  as twice the forward;
* head selection: the core's forward once on the round's first batch of
  B samples, and each of the k heads' forward on those features;
* gossip: the degree + 1 models a node mixes (its neighbours' and its
  own), two FLOPs per parameter each.

This is what the algorithm requires, not what an implementation computes
(a dense n x n mixing product, say), so a change of implementation cannot
move the yardstick.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def _conv(hw: int, k: int, cin: int, cout: int) -> int:
    return 2 * hw * hw * k * k * cin * cout


def _layer(layer: dict, hw: int) -> tuple[int, int, int]:
    """(forward FLOPs per sample, parameters, output side) of one entry of
    a configuration's ``layers``, given its input side ``hw``."""
    op = layer["op"]
    if op == "conv":
        k, cin, cout = layer["k"], layer["cin"], layer["cout"]
        out = hw // layer["stride"]
        return (_conv(out, k, cin, cout), k * k * cin * cout + 2 * cout,
                out // 2 if layer.get("pool") else out)
    if op == "block":
        cin, cout = layer["cin"], layer["cout"]
        out = hw // layer["stride"]
        proj = cin != cout
        return (_conv(out, 3, cin, cout) + _conv(out, 3, cout, cout)
                + (_conv(out, 1, cin, cout) if proj else 0),
                9 * cin * cout + 9 * cout * cout + 4 * cout
                + (cin * cout if proj else 0), out)
    if op == "dense":
        d_in, d_out = layer["din"], layer["dout"]
        return 2 * d_in * d_out, d_in * d_out + d_out, hw
    return 0, 0, hw                    # avgpool, flatten: no FLOPs counted


def _walk(model: dict):
    """Per layer: (in the head, FLOPs per sample, parameters)."""
    hw, in_head = model["image_size"], False
    for layer in model["layers"]:
        in_head |= layer["name"] in model["head"]
        f, p, hw = _layer(layer, hw)
        yield in_head, f, p


def forward(model: dict) -> tuple[int, int]:
    """(core, head) forward FLOPs per sample."""
    core = head = 0
    for in_head, f, _ in _walk(model):
        if in_head:
            head += f
        else:
            core += f
    return core, head


def params(model: dict) -> int:
    """Parameters of one node's model (weights, biases, GroupNorm)."""
    return sum(p for _, _, p in _walk(model))


def node_round(model: dict, *, local_steps: int, batch: int, k: int,
               degree: int) -> int:
    core, head = forward(model)
    sgd = local_steps * batch * 3 * (core + head)
    select = batch * (core + k * head)
    gossip = (degree + 1) * params(model) * 2
    return sgd + select + gossip


def round_flops(cell: dict) -> int:
    """FLOPs one round of the cell needs, all nodes together."""
    return sum(cell["clusters"]) * node_round(
        cell["model"], local_steps=cell["local_steps"],
        batch=cell["batch_size"], k=len(cell["clusters"]),
        degree=cell["degree"])


def peak(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return table[device_kind]
