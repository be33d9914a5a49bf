"""The benchmark's work-counting functions and its table of peaks."""
import pytest

from bench import spec, work
from bench.peaks import PEAKS, peak_for, roofline_share
from bench.stats import percentile

BENCH = spec.load_benchmark()
RESNET = spec.load_config(BENCH, "resnet50")
SC2 = spec.load_config(BENCH, "starcoder2-3b")


def test_resnet50_flops_per_image_is_twice_its_multiply_adds():
    # ResNet50 v1.5 at 224x224: about 4.1 G multiply-adds per image
    flops = work.resnet_flops_per_image(RESNET)
    assert flops == pytest.approx(2 * 4.1e9, rel=0.02)


def test_resnet50_flops_count_by_hand_at_a_small_size():
    cfg = dict(RESNET, image_size=32, stage_blocks=[1, 1, 1, 1],
               num_classes=10)
    # stem 16x16x(7*7*3)x64, then per stage (h, w_in -> mid -> out, stride)
    macs = 16 * 16 * 147 * 64
    h, cin = 8, 64
    for mid, stride in zip((64, 128, 256, 512), (1, 2, 2, 2)):
        ho = h // stride
        out = 4 * mid
        macs += h * h * cin * mid + ho * ho * 9 * mid * mid \
            + ho * ho * mid * out + ho * ho * cin * out
        h, cin = ho, out
    macs += 2048 * 10
    assert work.resnet_flops_per_image(cfg) == 2.0 * macs


def test_decode_flops_per_token_counts_matmuls_and_context():
    d, f, v, hd = 3072, 12288, 49152, 128
    per_layer = d * 24 * hd * 2 + 2 * d * 2 * hd + 2 * d * f
    params = 4 * per_layer + d * v
    assert work.lm_matmul_params(SC2) == params
    pos = 999
    expect = 2.0 * params + 4 * (4.0 * 24 * hd * (pos + 1))
    assert work.decode_flops_per_token(SC2, pos) == expect


def test_decode_attention_work_counts_only_the_needed_positions():
    flops, nbytes = work.decode_attention_work(SC2, [0, 9])
    kv_row = 2 * 2 * 128 * 4                 # K and V, 2 kv heads, f32
    q_bytes = 24 * 128 * 4
    assert nbytes == (1 + 10) * kv_row + 2 * (2 * q_bytes)
    assert flops == 4.0 * 24 * 128 * (1 + 10)
    # a longer cache behind the same positions is no more needed work
    assert work.decode_attention_work(
        dict(SC2, max_position_embeddings=8192), [0, 9]) == (flops, nbytes)


def test_peak_table_knows_the_v5e_and_refuses_other_kinds():
    p = peak_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peak"):
        peak_for("cpu")
    assert "cpu" not in PEAKS


def test_roofline_share_takes_the_larger_bound():
    p = peak_for("TPU v5 lite")
    share, bound = roofline_share(0.0, 819e9, 2.0, p)
    assert (share, bound) == (50.0, "memory")
    share, bound = roofline_share(197e12, 0.0, 4.0, p)
    assert (share, bound) == (25.0, "compute")


@pytest.mark.parametrize("values,q,expect", [
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 95, 3.85),
    ([5.0], 95, 5.0),
])
def test_percentile_interpolates_like_numpy(values, q, expect):
    assert percentile(values, q) == pytest.approx(expect)
