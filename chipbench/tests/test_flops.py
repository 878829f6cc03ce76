"""The FLOP counter against hand counts, walking configurations' layer
lists."""
import pytest

from chipbench import flops, reference, spec

TEST_CONFIGS = spec.HERE / "tests" / "data" / "configs"


def test_resnet8_forward_per_sample():
    core, head = flops.forward(spec.config("resnet8"))
    assert core == 3_538_944 + 37_748_736                 # stem, block1
    assert head == 29_360_128 + 29_360_128 + 5_248        # block2, 3, FC
    assert core + head == 100_013_184


def test_published_gn_lenet_layer_walk():
    """5x5 convolutions at 32, 32 and 64 channels over 32, 16 and 8
    pixels, then an FC of 1,024 -> 10: what a GN-LeNet configuration at
    the published widths would count. The walk gives convolutions no bias
    (the program's have none); decentralizepy's 128 biases are not in
    the parameter count."""
    model = spec.config("gn-lenet-published", TEST_CONFIGS)
    core, head = flops.forward(model)
    assert core == 4_915_200 + 13_107_200 + 6_553_600
    assert head == 20_480
    assert flops.params(model) == 89_706


@pytest.mark.parametrize("name,root", [
    ("resnet8", spec.CONFIGS), ("resnet8-16px", TEST_CONFIGS),
    ("gn-lenet-published", TEST_CONFIGS)])
def test_params_match_the_reference_shapes(name, root):
    model = spec.config(name, root)
    assert flops.params(model) == reference.param_count(model)


def test_node_round_counts_sgd_selection_and_sparse_gossip():
    m = spec.config("resnet8")
    f = flops.node_round(m, local_steps=10, batch=8, k=2, degree=4)
    sgd = 10 * 8 * 3 * 100_013_184
    select = 8 * (41_287_680 + 2 * 58_725_504)
    gossip = 5 * 79_865 * 2
    assert f == sgd + select + gossip
    cell = spec.workload("resnet8-facade-32")
    assert flops.round_flops(cell) == 32 * f          # about 0.81 TFLOP
    assert 0.80e12 < flops.round_flops(cell) < 0.82e12


def test_peaks_are_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v4")
