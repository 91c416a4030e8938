"""Model FLOPs and per-call roofline counts (``bench/counts.py``)."""
import json

import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on sys.path)
import counts
from bench_smoke import BENCH


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_faithful_forward_macs_per_layer():
    layers, _ = counts.conv_layers(_cfg("alexnet-faithful"))
    macs = [counts.conv_macs(l_) for l_ in layers]
    assert [round(m / 1e5) / 10 for m in macs] == \
        [105.4, 223.9, 149.5, 112.1, 74.8]
    assert round(sum(counts.fc_macs(_cfg("alexnet-faithful"))) / 1e5) / 10 \
        == 58.6


@pytest.mark.parametrize("name,total", [("alexnet-faithful", 1.0587e12),
                                        ("alexnet-ungrouped", 1.6898e12)])
def test_step_flops(name, total):
    got = counts.model_flops_per_step(_cfg(name), 256)
    assert abs(got - total) / total < 5e-5


def test_conv_calls_follow_dtype_and_cover_the_step():
    cfg = _cfg("alexnet-faithful")
    f32 = counts.conv_calls(cfg, 256, "float32")
    bf16 = counts.conv_calls(cfg, 256, "bfloat16")
    for a, b in zip(f32["fwd"] + f32["bwd"], bf16["fwd"] + bf16["bwd"]):
        assert a["flops"] == b["flops"]
        assert a["bytes"] == 2 * b["bytes"]
    # forward + both gradients of every conv but conv1's input gradient
    assert len(f32["fwd"]) == 5 and len(f32["bwd"]) == 9
    layers, _ = counts.conv_layers(cfg)
    conv_flops = sum(c["flops"] for c in f32["fwd"] + f32["bwd"])
    assert conv_flops == pytest.approx(
        2 * 256 * (3 * sum(counts.conv_macs(l_) for l_ in layers)
                   - counts.conv_macs(layers[0])))


def test_roofline_takes_the_binding_roof():
    peak = counts.peaks("TPU v5 lite")
    # bound by FLOPs, then by bytes
    call = {"flops": 197e12, "bytes": 819e9 / 2, "dtype": "bfloat16"}
    assert counts.roofline_s(call, peak) == pytest.approx(1.0)
    call = {"flops": 197e12 / 4, "bytes": 819e9, "dtype": "int8"}
    assert counts.roofline_s(call, peak) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
