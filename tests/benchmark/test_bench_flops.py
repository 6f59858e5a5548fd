"""``harness/flops.py`` against counts made by hand, and the peak table."""

import pytest

import bench_paths  # noqa: F401
from harness import flops, peaks


def test_resnet50_forward_is_twice_its_multiply_adds():
    macs = flops.resnet50_forward_macs()
    # the count every ResNet-50 table gives: 4.09 G multiply-adds at 224 px
    assert macs == pytest.approx(4.09e9, rel=0.005)
    assert 2 * macs == pytest.approx(8.2e9, rel=0.05)
    assert flops.resnet50_train_flops_per_image() == 3 * 2 * macs


def test_resnet50_by_hand_for_the_stem_and_the_head():
    # stem: 112 x 112 outputs of a 7 x 7 x 3 -> 64 convolution
    stem = 112 * 112 * 7 * 7 * 3 * 64
    head = 2048 * 1000
    # stage 1, block 0 at 56 x 56: 1x1 64->64, 3x3 64->64, 1x1 64->256, and
    # the projection 64->256
    block0 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert stem == 118013952 and block0 == 231211008
    assert flops.resnet50_forward_macs() > stem + head + block0
    # the stem's padding to 8 channels is not model work
    assert flops.resnet50_forward_macs(in_channels=8) - \
        flops.resnet50_forward_macs() == 112 * 112 * 49 * 5 * 64


def test_gpt2_small_matmul_flops_per_token():
    # a layer: q, k, v, out (4 x 768^2) and the MLP (2 x 768 x 3072);
    # the tied head: 768 x 50257
    per_layer = 4 * 768 * 768 + 2 * 768 * 3072
    want = 2 * (12 * per_layer + 768 * 50257)
    assert per_layer == 7077888
    assert flops.gpt_forward_matmul_flops_per_token(12, 768, 3072, 50257) \
        == want == 247064064


@pytest.mark.parametrize("seq,train_gflop_per_token", [
    (512, 0.7696), (1024, 0.7979), (8192, 1.1942)])
def test_gpt2_small_train_flops_per_token(seq, train_gflop_per_token):
    # causal attention: T(T+1)/2 pairs, two products of 768 multiply-adds a
    # pair and layer, forward; training is three times the forward
    pairs = seq * (seq + 1) // 2
    attention = 12 * 2 * 2 * pairs * 768
    assert flops.gpt_forward_attention_flops_per_seq(12, 768, seq) == attention
    want = 3 * (247064064 + attention / seq)
    got = flops.gpt_train_flops_per_token(12, 768, 3072, 50257, seq)
    assert got == pytest.approx(want)
    assert got / 1e9 == pytest.approx(train_gflop_per_token, rel=1e-3)


def test_attention_share_at_8192():
    matmul = 3 * 247064064
    total = flops.gpt_train_flops_per_token(12, 768, 3072, 50257, 8192)
    assert (total - matmul) / total == pytest.approx(0.38, abs=0.01)


@pytest.mark.parametrize("kernel,products,arrays,rows", [
    ("_fwd_kernel", 2, 4, 1), ("_bwd_dq_kernel", 3, 5, 2),
    ("_bwd_dkv_kernel", 4, 6, 2)])
def test_flash_kernel_cost(kernel, products, arrays, rows):
    batch, seq, heads, dim = 2, 8192, 12, 64
    pairs = batch * heads * seq * (seq + 1) // 2
    got_flops, got_bytes = flops.flash_kernel_cost(kernel, batch, seq, heads,
                                                   dim, True)
    assert got_flops == products * 2 * dim * pairs
    assert got_bytes == batch * heads * seq * (arrays * dim * 2 + rows * 4)
    full, _ = flops.flash_kernel_cost(kernel, batch, seq, heads, dim, False)
    assert full == products * 2 * dim * batch * heads * seq * seq


def test_roofline_says_which_peak_bounds():
    v5e = peaks.load("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(197e12, 1e9, v5e)
    assert (seconds, bound) == (pytest.approx(1.0), "compute")
    seconds, bound = flops.roofline_seconds(1e9, 819e9, v5e)
    assert (seconds, bound) == (pytest.approx(1.0), "memory")
    cost = flops.flash_kernel_cost("_fwd_kernel", 2, 8192, 12, 64, True)
    assert flops.roofline_seconds(*cost, v5e)[1] == "compute"


def test_peaks_of_the_v5e():
    v5e = peaks.load("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "source" in v5e


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", ""])
def test_an_unknown_device_kind_raises(kind):
    with pytest.raises(peaks.UnknownDevice, match="not in peaks.json"):
        peaks.load(kind)
