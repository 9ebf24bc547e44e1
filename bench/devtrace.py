"""Reduction of a ``--trace 1`` profile to what the per-layer metrics
read: device busy time, device time per step program, the top device
operations (with how often each ran), and each idle gap of the device attributed to the host span
that was open across it.

The profile is read with ``jax.profiler.ProfileData`` and flattened to
plain event tuples first (``events``), so the reduction itself
(``reduce``) works on lists and is tested on a small recorded sample.

Conventions of the profile this relies on (TPU): a device is a plane
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
operation run and its ``XLA Modules`` line one event per program run,
named after the jitted function (``jit__decode_impl(...)``).  Host
spans are the harness's ``jax.profiler.TraceAnnotation`` events, all
named ``bench.*``; ``bench.window`` spans the traced window.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# (plane, line, name, start_ns, end_ns)
Event = Tuple[str, str, str, float, float]

# the engine's step programs, by the name jit gives them
PROGRAMS = {
    "decode": re.compile(r"(?<![a-z])_decode_impl"),
    "prefill": re.compile(r"_prefill_chunk(_ssm)?_impl"),
}


def events(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``
    that the reduction can use: device ops and programs, and the
    harness's host spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.end_ns)))
    return out


_HLO = re.compile(r"%?([\w.-]+) = (.+?) ([a-z][\w-]*)\(")


def op_name(name: str) -> str:
    """A device op's event name is its whole HLO instruction; keep the
    op, its first result's type and the instruction's own name."""
    m = _HLO.match(name)
    if m is None:
        return name[:80]
    first = m.group(2).lstrip("(").split("{")[0].split(",")[0]
    if "[" in first and "]" not in first:
        first = m.group(2).lstrip("(").split("]")[0] + "]"
    return f"{m.group(3)} {first} ({m.group(1)})"


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(evs: List[Event], top: int = 10) -> Optional[dict]:
    """The traced window's device numbers, or None when the trace holds
    no window or no device operation."""
    win = [(s, e) for _, _, n, s, e in evs if n == "bench.window"]
    devices = sorted({p for p, line, *_ in evs if line == "XLA Ops"})
    if not win or not devices:
        return None
    lo, hi = win[0]
    busy_ns = 0.0
    busy_iv: List[Tuple[float, float]] = []
    for dev in devices:
        iv = _union(_clip([(s, e) for p, line, _, s, e in evs
                           if p == dev and line == "XLA Ops"], lo, hi))
        busy_ns += sum(b - a for a, b in iv)
        if dev == devices[0]:
            busy_iv = iv
    ops: Dict[str, float] = defaultdict(float)
    runs: Dict[str, int] = defaultdict(int)
    programs = {k: [0, 0.0] for k in PROGRAMS}
    for p, line, name, s, e in evs:
        if p != devices[0] or e <= lo or s >= hi:
            continue
        if line == "XLA Ops":
            ops[op_name(name)] += (min(e, hi) - max(s, lo)) / 1e9
            runs[op_name(name)] += 1
        elif line == "XLA Modules":
            for kind, rx in PROGRAMS.items():
                if rx.search(name):
                    programs[kind][0] += 1
                    programs[kind][1] += (min(e, hi) - max(s, lo)) / 1e9
    # idle gaps of the first device, each put down to the innermost
    # host span open at its midpoint
    spans = [(s, e, n) for _, _, n, s, e in evs
             if n.startswith("bench.") and n != "bench.window"]
    gaps: Dict[str, float] = defaultdict(float)
    edges = [lo] + [x for ab in busy_iv for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        gaps[min(open_)[1] if open_ else "host.untraced"] += (b - a) / 1e9
    rank = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": busy_ns / len(devices) / 1e9,
            "devices": len(devices),
            "programs": {k: {"runs": n, "seconds": s}
                         for k, (n, s) in programs.items()},
            "device_ops": rank(ops),
            "op_runs": {k: runs[k] for k, _ in rank(ops)},
            "idle_gaps": rank(gaps)}
