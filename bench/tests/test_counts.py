"""FLOP and byte counts against hand-worked values, and the peak table."""
import pytest

from bench import flops, peaks, spec
from bench.tests.cells import _load

CONFIGS = {name: _load("configs", name + ".json")
           for name in ("qwen2-vl-72b.stage", "qwen1.5-4b")}
MODELS = {name: spec.family_module(cfg) for name, cfg in CONFIGS.items()}
M1, M4 = MODELS["qwen2-vl-72b.stage"], MODELS["qwen1.5-4b"]
R1 = M1.sizes(CONFIGS["qwen2-vl-72b.stage"])
Q4 = M4.sizes(CONFIGS["qwen1.5-4b"])


def test_matmul_parameters_per_layer():
    # qwen2-vl-72b: q 8192x8192, k/v 8192x1024 each, o 8192x8192, MLP 3x8192x29568
    assert M1.layer_matmul_params(R1) == 67108864 * 2 + 2 * 8388608 + 3 * 242221056
    # qwen1.5-4b: four 2560x2560 projections and 3x2560x6912
    assert M4.layer_matmul_params(Q4) == 4 * 6553600 + 3 * 17694720


def test_frozen_backbone_round_counts_4n_per_position():
    n = R1.layers * M1.layer_matmul_params(R1)
    s = 320
    attn = R1.layers * 3 * 4 * 64 * 128 * s * s / 2
    adapters = 12 * 8192 * 64 * s                   # text and image rows adapted
    connector = 2 * 1280 * 8192 * 64
    one = M1.round_flops(R1, sequences=1, text_len=256, image_len=64,
                         loss_positions=10)
    assert one == pytest.approx(4 * n * s + attn + adapters + connector
                                + 10 * 4 * 8192 * 19008)
    # weight gradients of the frozen layers (another 2N) are not counted
    assert one < 5 * n * s


def test_served_weight_bytes():
    # 40 layers of 79.3 M params, the head table and the final norm, in bf16
    per_layer = 4 * 6553600 + 3 * 17694720 + 3 * 2560 + 2 * 2560
    assert M4.weight_bytes(Q4) == 2 * (40 * per_layer + 151936 * 2560 + 2560)


def test_decode_reads_live_kv_up_to_each_position():
    f0, b0 = M4.decode_cost(Q4, [])
    f, b = M4.decode_cost(Q4, [9, 99])
    kv_pos = 40 * 2 * 20 * 128 * 2       # 409,600 B per position
    assert kv_pos == 409600
    assert b - b0 == kv_pos * (10 + 100)
    n = 40 * M4.layer_matmul_params(Q4)
    assert f == pytest.approx(2 * (2 * n + 2 * 2560 * 151936 + 4 * 2560 * 64)
                              + 40 * 4 * 20 * 128 * (10 + 100))


def test_prefill_counts_real_positions_only():
    f100, b100 = M4.prefill_cost(Q4, 100)
    f200, _ = M4.prefill_cost(Q4, 200)
    n = 40 * M4.layer_matmul_params(Q4)
    assert f200 - f100 == pytest.approx(2 * n * 100 + 40 * 2 * 20 * 128 * (200**2 - 100**2)
                                        + 4 * 2560 * 64 * 100)
    assert b100 == M4.weight_bytes(Q4) + 409600 * 100


def test_flash_attention_counts():
    f, b = flops.flash_cost(10, 64, 8, 320, 320, 128, backward=False)
    assert f == 4 * 10 * 64 * 320 * 320 * 128 / 2
    qo, kv, lse = 10 * 64 * 320 * 128 * 2, 10 * 8 * 320 * 128 * 2, 10 * 64 * 320 * 4
    assert b == 2 * qo + 2 * kv + lse
    fb, bb = flops.flash_cost(10, 64, 8, 320, 320, 128, backward=True)
    assert fb == 3 * f
    assert bb == b + 4 * qo + 4 * kv + lse


# Each full-size configuration's counts, pinned to the bit: a change to a
# counting rule or to the sizes a family reads shows here first. The
# arguments are a round of 20 or 192 sequences, a 300-position prefill, a
# decode step over slots at positions 0, 17 and 640, and one flash launch
# at the cell's shapes (silo-vqa: 5 clients x 2 rows, 64 heads, 320
# positions padded to 384; xdevice: 64 clients x 1 row, 20 heads, 64).
PINNED = {
    "qwen2-vl-72b.stage": {
        "round": ((20, 256, 64, 57), 158083588292608.0),
        "prefill": (3697426563072.0, 12607631360),
        "decode": (37953601536.0, 12617953280.0),
        "weight_bytes": 12599029760, "kv_bytes_per_position": 28672,
        "flash": (([5, 2, 64, 384, 128], 320), (16777216000.0, 118784000)),
    },
    "qwen1.5-4b": {
        "round": ((192, 64, 0, 1021), 158003152814080.0),
        "prefill": (1922571960320.0, 7245706240),
        "decode": (21637693440.0, 7393162240.0),
        "weight_bytes": 7122826240, "kv_bytes_per_position": 409600,
        "flash": (([64, 1, 20, 64, 128], 64), (1342177280.0, 84213760)),
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_full_size_counts_are_pinned(name):
    model, want = MODELS[name], PINNED[name]
    sz = model.sizes(CONFIGS[name])
    (seqs, text, image, loss), flops_ = want["round"]
    assert model.round_flops(sz, sequences=seqs, text_len=text, image_len=image,
                             loss_positions=loss) == flops_
    assert model.prefill_cost(sz, 300) == want["prefill"]
    assert model.decode_cost(sz, [0, 17, 640]) == want["decode"]
    assert model.weight_bytes(sz) == want["weight_bytes"]
    assert model.kv_bytes_per_position(sz) == want["kv_bytes_per_position"]
    (dims, seq), cost = want["flash"]
    assert model.flash_launch_cost(sz, dims, seq) == cost


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
