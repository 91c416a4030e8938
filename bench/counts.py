"""Operations and bytes, from a call's own shapes and dtypes.

``model_flops_per_step``: the convolutions and matrix products of the
forward and backward passes (2 FLOPs per multiply-add), without the
first conv's input gradient, which nothing needs.  ``conv_calls``: one
entry per convolution the step runs — the fused Pallas forward of each
layer, and the input- and weight-gradient convolutions of its backward —
with FLOPs and the bytes a call must move at least (each operand read
once, the result written once, nothing padded).  ``roofline_s`` is the
least time a call could take on a device: the larger of its FLOPs over
the peak rate for its dtype and its bytes over the memory bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
            "float8_e4m3fn": 1}

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def conv_layers(cfg: dict) -> tuple:
    """([per conv layer: input (H, W, Cin), output (OH, OW, Cout), kernel,
    stride, padding, groups], features into the first FC layer)."""
    out, hw, c_in = [], cfg["crop"], cfg["in_channels"]
    for cs in cfg["convs"]:
        oh = (hw + 2 * cs["padding"] - cs["kernel"]) // cs["stride"] + 1
        out.append({"in": (hw, hw, c_in),
                    "out": (oh, oh, cs["out_channels"]),
                    "kernel": cs["kernel"], "stride": cs["stride"],
                    "padding": cs["padding"], "groups": cs["groups"]})
        hw = (oh - 3) // 2 + 1 if cs["pool"] else oh
        c_in = cs["out_channels"]
    out_hw = hw
    return out, out_hw * out_hw * c_in


def conv_macs(layer: dict) -> int:
    """Multiply-adds of one image through one conv layer."""
    oh, ow, cout = layer["out"]
    cig = layer["in"][2] // layer["groups"]
    return oh * ow * cout * layer["kernel"] ** 2 * cig


def fc_macs(cfg: dict) -> list:
    _, flat = conv_layers(cfg)
    return [flat * cfg["fc_dim"], cfg["fc_dim"] * cfg["fc_dim"],
            cfg["fc_dim"] * cfg["n_classes"]]


def model_flops_per_step(cfg: dict, batch: int) -> float:
    """Forward + backward FLOPs of one step over ``batch`` images."""
    layers, _ = conv_layers(cfg)
    fwd = sum(conv_macs(l_) for l_ in layers) + sum(fc_macs(cfg))
    return 2.0 * batch * (3 * fwd - conv_macs(layers[0]))


def conv_calls(cfg: dict, batch: int, dtype: str = "float32") -> dict:
    """{"fwd": [...], "bwd": [...]} — per call ``{"layer", "flops",
    "bytes", "dtype"}`` for one step over ``batch`` images per chip."""
    isz = ITEMSIZE[dtype]
    layers, _ = conv_layers(cfg)
    fwd, bwd = [], []
    for i, l_ in enumerate(layers):
        macs = batch * conv_macs(l_)
        x = batch * int(np.prod(l_["in"]))
        y = batch * int(np.prod(l_["out"]))
        w = l_["kernel"] ** 2 * (l_["in"][2] // l_["groups"]) \
            * l_["out"][2]
        name = f"conv{i + 1}"
        # forward: read x, w, bias; write y
        fwd.append({"layer": name, "flops": 2.0 * macs,
                    "bytes": float((x + w + l_["out"][2] + y) * isz),
                    "dtype": dtype})
        # weight gradient: read x and dy, write dw
        bwd.append({"layer": name + ".dw", "flops": 2.0 * macs,
                    "bytes": float((x + y + w) * isz), "dtype": dtype})
        if i:
            # input gradient: read dy and w, write dx
            bwd.append({"layer": name + ".dx", "flops": 2.0 * macs,
                        "bytes": float((y + w + x) * isz), "dtype": dtype})
    return {"fwd": fwd, "bwd": bwd}


def roofline_s(call: dict, peak: dict) -> float:
    """Least seconds ``call`` could take on a device with ``peak``."""
    t_flops = call["flops"] / peak["flops_per_s"][call["dtype"]]
    t_bytes = call["bytes"] / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes)
