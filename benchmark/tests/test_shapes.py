"""FLOP and byte functions against hand counts, both configurations."""
import pytest

from benchmark import harness, peaks, shapes


def test_train_flops_per_token_bert():
    cfg = harness.load_config("bert_osdi22")
    # a layer: q, k, v, o = 4 * 1024^2; FFN = 2 * 1024 * 4096
    layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    assert shapes.layer_matmul_params(cfg) == layer == 12_582_912
    fwd = 2 * (12 * layer + 1024 * 2) + 12 * 4 * 512 * 1024
    assert shapes.forward_flops_per_token(cfg, 512) == fwd
    assert shapes.train_flops_per_token(cfg) == 3 * fwd
    assert 0.97e9 < shapes.train_flops_per_token(cfg) < 0.99e9  # ~0.98 GFLOP


def test_flash_attention_counts():
    cfg = harness.load_config("bert_osdi22")
    flops, nbytes = shapes.flash_attention_train(
        cfg, {"sequences_per_step_per_chip": 64})
    fwd = 4 * 512 * 512 * 1024          # QK^T and PV, one sequence, one layer
    assert flops == 12 * 64 * 3 * fwd
    assert nbytes == 12 * (64 * 512 * 1024 * 2) * 12
    # compute-bound on a v5e: the side the roofline names
    _t, side = peaks.least_time_s(flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert side == "mxu"


def test_decode_attention_counts_filled_rows_only():
    cfg = harness.load_config("lm_osdi22w")
    flops, nbytes = shapes.decode_attention(cfg, {"decode_attended_rows": 1000})
    assert flops == 12 * 4 * 1000 * 1024
    assert nbytes == 12 * 2 * 1000 * 1024 * 4     # K and V, float32
    _t, side = peaks.least_time_s(flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert side == "hbm"


def test_serve_forward_flops():
    cfg = harness.load_config("lm_osdi22w")
    layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    base = 2 * (12 * layer + 1024 * 30522)
    work = {"prompt_tokens": 10, "decode_tokens": 5,
            "prefill_attended_rows": 55, "decode_attended_rows": 60}
    assert shapes.serve_forward_flops(cfg, work) == (
        15 * base + 12 * 4 * 115 * 1024)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops_per_s, v5e.hbm_bytes_per_s, v5e.hbm_bytes) == (
        197e12, 819e9, 16e9)
