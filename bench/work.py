"""The work a step of the engine needs, and the least time the chip can
take for it.

Work is what the algorithm needs, computed from the configuration's
shapes and the step's own rows (``bench/arch/<arch>.py`` holds each
architecture's formulas): FLOPs of every matmul and attention product
over the real tokens, and HBM bytes of the weights read once, the KV or
state read and written, and the embedding rows gathered.  Padding rows,
padded chunk positions and any copy the implementation makes are not
work, so a change that removes them is read against the same numbers.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from unit import arch_module

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({', '.join(table)})")
    return table[device_kind]


def step_work(llm: dict, kind: str, detail, dtype_bytes: int
              ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step: ``detail`` is the rows' context
    lengths for a decode step, (offset, tokens) per row for a prefill
    chunk."""
    arch = arch_module(llm["arch"])
    fn = arch.decode_work if kind == "decode" else arch.prefill_work
    return fn(llm["config"], detail, dtype_bytes)


def least_time(flops: float, bytes_: float, peak: dict) -> Dict[str, float]:
    """The roofline: the larger of compute time and memory time."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "compute_s": t_c, "memory_s": t_m}
