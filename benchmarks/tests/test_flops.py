"""``flops.py`` against counts made by hand."""

from benchmarks import flops, peaks

ENC = {"hidden_size": 384, "intermediate_size": 1536, "num_hidden_layers": 6}


def test_one_encoder_layer_by_hand():
    # 20 tokens, d=384, ff=1536
    t, d, ff = 20, 384, 1536
    qkvo = 4 * (2 * t * d * d)  # four d x d projections
    scores = 2 * t * t * d  # 12 heads x (t x 32) @ (32 x t)
    values = 2 * t * t * d
    mlp = 2 * t * d * ff + 2 * t * ff * d
    assert qkvo == 23_592_960 and mlp == 47_185_920 and scores == 307_200
    assert flops.layer_flops(t, d, ff) == qkvo + scores + values + mlp == 71_393_280
    assert flops.encoder_flops([t], ENC) == 6 * 71_393_280


def test_one_cross_encoder_pair_by_hand():
    t, d = 50, 384
    layer = 4 * 2 * t * d * d + 2 * 2 * t * t * d + 2 * 2 * t * d * 1536
    head = 2 * d * d + 2 * d
    assert flops.cross_encoder_flops([t], ENC) == 6 * layer + head == 1_085_018_880


def test_one_rescore_call_by_hand():
    # one query, 69 probes of 256 padded rows of 384 float32
    assert flops.rescore_flops(1, 69, 256, 384) == 2 * 69 * 256 * 384 == 13_565_952
    assert flops.rescore_bytes(1, 69, 256, 384, 4) == 69 * 256 * 384 * 4 == 27_131_904
    assert flops.probe_flops(1, 8334, 384) == 2 * 8334 * 384
    least = flops.roofline_seconds(13_565_952, 27_131_904, peaks.peaks_for("TPU v5 lite"))
    assert least["bound"] == "hbm_bytes_per_s"
    assert abs(least["seconds"] - 27_131_904 / 819e9) < 1e-12


def test_unknown_device_is_an_error():
    import pytest

    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
