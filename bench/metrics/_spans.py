"""The serving path's own span record (``repro.serving.metrics.SPANS``),
read in-process after the window and put onto it by the ticks both
sides recorded: the program's ``mux.tick`` spans and the harness's
``ctx.ticks`` are one to one and in the same order, so the i-th tick's
place in the window decides where every span inside it belongs.

A program without the record (before it had spans), a record that
overflowed, or tick counts that differ read as None: no partial
number."""
from __future__ import annotations

import numpy as np


class Aligned:
    """The record with each entry's tick (its ordinal among the ticks,
    -1 outside every tick) and which ticks lie in the window."""

    def __init__(self, view, ticks, in_window):
        self.v = view
        self.names = [view.names[i] for i in view.name]
        self.dur = view.t1 - view.t0
        self.ticks = ticks
        self.in_window = in_window
        # a parent opens before its children, so one pass in entry
        # order hands each entry its enclosing tick
        ordinal = {int(t): k for k, t in enumerate(ticks)}
        self.tick = np.full(len(self.names), -1)
        for j, p in enumerate(view.parent):
            self.tick[j] = ordinal[j] if j in ordinal else (
                self.tick[p] if p >= 0 else -1)

    def windowed(self, i) -> bool:
        return self.tick[i] >= 0 and bool(self.in_window[self.tick[i]])

    def step_roots(self, kind):
        """Entries of each ``mux.<kind>.<owner>`` step (not its phases)."""
        pre = f"mux.{kind}."
        return [i for i, n in enumerate(self.names) if n.startswith(pre)
                and (self.v.parent[i] < 0
                     or not self.names[self.v.parent[i]].startswith(pre))]

    def children(self, i):
        return np.flatnonzero(self.v.parent == i)


def aligned(ctx):
    try:
        from repro.serving.metrics import SPANS
    except ImportError:
        return None
    view = SPANS.view()
    if view is None or "mux.tick" not in view.names:
        return None
    ticks = np.flatnonzero(view.name == view.names.index("mux.tick"))
    if len(ticks) != len(ctx.ticks) or not len(ticks) \
            or np.isnan(view.t1[ticks]).any():
        return None
    in_window = np.array([0.0 <= t.t0 < ctx.seconds for t in ctx.ticks])
    return Aligned(view, ticks, in_window)
