"""The analytic round counts against XLA's own count, and the peak table."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import peaks
from bench.flops.resnet import Conv, convs, round_count
from bench.reference import resnet as ref

NET = {"channels": [64, 64, 64, 128, 256, 512], "strides": [1, 1, 1, 2, 2, 2],
       "image_size": 32, "num_classes": 10}


def test_block_forward_flops_match_xla():
    """Layer 4 (64 -> 128, stride 2, with its projection) at batch 8."""
    cin, cout, size, batch = 64, 128, 32, 8
    params, _ = ref.init_network(0, NET["channels"], 10)
    p = params["layer4"]

    def block(x, p):
        h = ref._conv(x, p["conv1"], 2)
        h = ref._conv(h, p["conv2"], 1)
        return h + ref._conv(x, p["proj"], 2)

    x = jax.ShapeDtypeStruct((batch, size, size, cin), jnp.float32)
    cost = jax.jit(block).lower(x, p).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = sum(c.flops(batch) for c in convs(NET["channels"], NET["strides"],
                                              size, 3, 4))
    # XLA adds the elementwise sum (one flop per output element)
    adds = batch * 16 * 16 * cout
    assert ours + adds == pytest.approx(cost["flops"], rel=1e-3)


def test_first_convolution_of_a_segment_has_no_input_gradient():
    server = convs(NET["channels"], NET["strides"], 32, 3, 6)
    assert [c.first for c in server[:3]] == [True, False, True]
    c = server[0]
    assert c.train_flops(64) == 2 * c.flops(64)
    assert server[1].train_flops(64) == 3 * server[1].flops(64)


def test_round_count_scales_with_image_area():
    job = {"splits": [3] * 4 + [4] * 4 + [5] * 4, "batch": 64,
           "local_epochs": 1}
    cifar = round_count(NET, job)
    stl = round_count({**NET, "strides": [2, 1, 1, 2, 2, 2],
                       "image_size": 96}, job)
    assert 1.2e12 < cifar.flops < 1.5e12
    assert 2.0 < stl.flops / cifar.flops < 2.5
    assert Conv(8, 8, 3, 4, 4, True).bytes(1) > 0


def test_peaks_refuse_an_unknown_device(tmp_path):
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("cpu")
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(KeyError):
        peaks.peaks("TPU v5 lite", str(path))
