"""``bench/arch/granite4h.py``'s counts against counts worked out by
hand for one decode step of the granite cell's share (20 layers: 18
Mamba-2, 2 attention; 9 of 72 experts held, top-10; shared expert)."""
import json
import os

from work import step_work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _llm():
    with open(os.path.join(BENCH, "configs", "granite4h-small.json")) as f:
        return json.load(f)["llms"][0]


def test_decode_step_one_row_at_context_100():
    flops, bytes_ = step_work(_llm(), "decode", [100], 2)
    d, V = 4096, 100352
    # Mamba-2: in_proj 4096 x (2x8192 + 2x128 + 128), out_proj 8192 x 4096
    mamba = d * 16768 + 8192 * d
    conv = 8192 + 256
    # attention: q 32x128, k and v 8x128 each, o
    attn = d * (32 + 16) * 128 + 32 * 128 * d
    # per layer: router over all 72 experts and the shared expert (1536)
    ffn = d * 72 + 3 * d * 1536
    # one token reaches 10 of 72 experts: 10 x 9 / 72 = 1.25 held pairs
    pairs = 1.25
    expert = 3 * d * 768
    tok = (18 * (2 * mamba + 2 * 4 * conv + 5 * 128 * 64 * 128 + 2 * 8192)
           + 2 * 2 * attn + 20 * 2 * ffn + 2 * d * V)
    assert flops == tok + 4 * 2 * 32 * 128 * 100 + 20 * pairs * 2 * expert
    # bytes: the weights outside the routed experts (router in float32;
    # Mamba-2's A, dt bias and D in float32), the held experts a token
    # reaches (9 x 10/72 of them), state in and out, KV of 100
    # positions, one embedding row
    weights = (2 * (18 * (mamba + 5 * conv + 8192) + 2 * attn
                    + 20 * (2 * d + 3 * d * 1536) + V * d + d)
               + 4 * (20 * d * 72 + 3 * 18 * 128))
    held = 20 * 9 * (10 / 72) * expert * 2
    state = 18 * (4 * 128 * 64 * 128 + 2 * 3 * conv)
    kv = 100 * 2 * 2 * 8 * 128 * 2
    assert bytes_ == weights + held + 2 * state + kv + d * 2


def test_top_level_keys_are_the_model_as_run():
    """The file gives the published keys as run at its top level, beside
    the harness's ``llms`` entry; the two must not drift apart."""
    with open(os.path.join(BENCH, "configs", "granite4h-small.json")) as f:
        conf = json.load(f)
    run = conf["llms"][0]["config"]
    assert {k: conf[k] for k in run} == run
