"""``bench/work.py``'s counts against counts worked out by hand for one
decode step of each model at its published sizes."""
import json
import os

from work import least_time, peaks, step_work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _llm(config, name):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        return next(m for m in json.load(f)["llms"] if m["name"] == name)


def test_phi3_decode_step_one_row_at_context_100():
    llm = _llm("phi3v-mamba2", "phi-3-vision-4.2b")
    flops, bytes_ = step_work(llm, "decode", [100], 2)
    # per layer: q, k, v 3072x(3x3072), o 3072x3072, MLP 3x3072x8192
    layer = 3 * 3072 * 3072 + 3072 * 3072 + 3 * 3072 * 8192   # 113,246,208
    assert layer == 113_246_208
    # 2 FLOPs per weight over 32 layers and the 3072x32064 head, plus
    # scores and mixing: 2 x 2 x 32 heads x 96 x 100 positions x 32 layers
    assert flops == 2 * (32 * layer + 3072 * 32064) + 4 * 32 * 32 * 96 * 100
    assert flops == 7_484_080_128
    # bf16 weights (layers with two norms each, head, final norm), the KV
    # of 99 earlier tokens read and one written (32 layers x K,V x 32
    # heads x 96 x 2 B = 393,216 B a token), one embedding row
    weights = 2 * (32 * (layer + 2 * 3072) + 3072 * 32064 + 3072)
    assert bytes_ == weights + 100 * 393_216 + 3072 * 2
    assert bytes_ == 7_484_485_632


def test_mamba2_decode_step_one_row():
    llm = _llm("phi3v-mamba2", "mamba2-2.7b")
    flops, bytes_ = step_work(llm, "decode", [100], 2)
    # in_proj 2560 x (2x5120 + 2x128 + 80), out_proj 5120 x 2560
    layer = 2560 * 10576 + 5120 * 2560                      # 40,181,760
    # per token and layer: matmuls, conv (4 taps over 5376 channels),
    # state update and readout (5 x 80 x 64 x 128), gate
    tok_layer = 2 * layer + 2 * 4 * 5376 + 5 * 80 * 64 * 128 + 2 * 5120
    assert tok_layer == 83_693_568
    # the head: 50277 tokens padded to a multiple of 16, 50288 rows
    assert flops == 64 * tok_layer + 2 * 2560 * 50288 == 5_613_862_912
    # weights: projections, conv weight and bias (5 x 5376), gate norm,
    # layer norm in bf16; A, dt bias, D in float32; tied head; final norm
    weights = 2 * (64 * (layer + 5 * 5376 + 5120 + 2560)
                   + 50288 * 2560 + 2560) + 4 * 3 * 64 * 80
    # state read and written: float32 [80, 64, 128] and a bf16 conv tail
    # of 3 x 5376, per layer
    state = 64 * (4 * 80 * 64 * 128 + 2 * 3 * 5376)
    assert bytes_ == weights + 2 * state + 2560 * 2 == 5_744_908_288


def test_least_time_takes_the_larger_bound():
    peak = peaks("TPU v5 lite")
    t = least_time(197e12, 819e9 * 2, peak)
    assert t["compute_s"] == 1.0 and t["memory_s"] == 2.0
    assert t["seconds"] == 2.0


def test_unknown_device_kind_is_refused():
    import pytest
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
