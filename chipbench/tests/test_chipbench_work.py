"""The work each call requires, against counts made by hand, and the table
of peaks."""
import pytest

from chipbench import bench, peaks, work

GRANITE = bench.load_json(bench.PKG / "configs" / "granite-moe-1b-a400m.json")["model"]
OLMOE = bench.load_json(bench.PKG / "configs" / "olmoe-1b-7b-pp2.json")["model"]


def test_granite_decode_step_by_hand():
    # per layer: wq 1024x1024 + wk, wv 1024x512 + wo 1024x1024 = 3,145,728;
    # router 1024x32 = 32,768; 8 experts x 3 x 1024 x 512 = 12,582,912
    per_layer = 3_145_728 + 32_768 + 12_582_912
    active = 24 * per_layer + 1024 * 49155          # + the tied head
    assert work.active_matmul_params(GRANITE) == active
    rows, context = 64, 64 * 1000
    w = work.decode_step(GRANITE, rows, context)
    attn = 4 * context * 16 * 64 * 24
    assert w.flops == pytest.approx(2 * rows * active + attn)
    # bytes: every weight once (64 tokens touch all but 32 * (3/4)^64 of the
    # 32 experts), router in float32, K/V of every context token
    experts = 32 * (1 - (1 - 8 / 32) ** 64)
    weights = 24 * (3_145_728 * 2 + 32_768 * 4 + experts * 1_572_864 * 2) \
        + 1024 * 49155 * 2
    kv = context * 24 * 2 * 8 * 64 * 2
    assert work.kv_bytes_per_token(GRANITE) == 49_152
    assert w.bytes == pytest.approx(weights + kv)


def test_olmoe_prefill_by_hand():
    # per layer: 4 x 2048 x 2048 attention, router 2048 x 64, 8 experts of
    # 3 x 2048 x 1024; head 2048 x 50304 untied
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    s = 300
    w = work.prefill(OLMOE, s)
    attn = 4 * (s * (s + 1) / 2) * 16 * 128 * 8
    assert w.flops == pytest.approx(2 * s * 8 * per_layer + 2 * 2048 * 50304 + attn)
    assert work.kv_bytes_per_token(OLMOE) == 65_536
    weights = 8 * (4 * 2048 * 2048 * 2 + 2048 * 64 * 4
                   + work.distinct_experts(OLMOE, s) * 3 * 2048 * 1024 * 2) \
        + 2048 * 50304 * 2
    assert w.bytes == pytest.approx(weights + s * 65_536)


def test_roofline_names_the_bound():
    peak = peaks.peak("TPU v5 lite")
    t, bound = work.roofline_seconds(work.decode_step(GRANITE, 64, 64 * 1000), peak)
    assert bound == "memory"
    t, bound = work.roofline_seconds(work.prefill(GRANITE, 3072), peak)
    assert bound == "compute" and t > 0


def test_peaks_table():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
