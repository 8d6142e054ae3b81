"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace (``*.xplane.pb``) holds one plane per device (``/device:TPU:<n>``),
whose op line lists every operation that ran there with its start and
duration, and the host plane, whose lines hold the benchmark's own spans
(``jax.profiler.TraceAnnotation``): ``window`` around the measured steps
and ``schedule``, ``prep``, ``dispatch`` and ``wait`` inside it.  All times
are nanoseconds on one clock.

Busy time is the union of the intervals in which an operation runs, so
overlapping events count once; idle share is 1 - busy / window.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

HOST_SPANS = ("window", "schedule", "prep", "dispatch", "wait")
#: lines of a device plane that summarise ops rather than list them
_SUMMARY_LINES = ("XLA Modules", "Steps", "Framework Ops",
                  "Framework Name Scope", "Source code")


@dataclass
class Trace:
    window: tuple                         # (start_ns, end_ns)
    spans: list                           # [(name, start_ns, end_ns)]
    ops: dict = field(default_factory=dict)   # device -> [(name, s, e)]

    # ------------------------------------------------------------------ #
    def clipped(self, device):
        w0, w1 = self.window
        return [(n, max(s, w0), min(e, w1)) for n, s, e in self.ops[device]
                if e > w0 and s < w1]

    def busy_ns(self, device) -> float:
        return sum(e - s for s, e in union((s, e) for _, s, e in
                                           self.clipped(device)))

    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return (sum(self.busy_ns(d) for d in self.ops) / len(self.ops)
                / 1e9)

    def kernel(self, name: str):
        """(count, seconds) of the device events whose name holds ``name``,
        over every device, inside the window."""
        n, t = 0, 0.0
        for d in self.ops:
            for op, s, e in self.clipped(d):
                if name in op:
                    n += 1
                    t += e - s
        return n, t / 1e9

    def top_ops(self, k: int = 10) -> list:
        """[[name, seconds]] of the ``k`` ops that took most device time in
        the window, summed over devices.  Only innermost events count (a
        ``while`` holds the ops of its body), named by their HLO
        instruction (the text before `` = ``)."""
        tot: dict = {}
        for d in self.ops:
            for op, s, e in leaves(self.clipped(d)):
                op = op.split(" = ")[0]
                tot[op] = tot.get(op, 0.0) + (e - s) / 1e9
        return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])
                [:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """[[host span, seconds]] of the ``k`` longest intervals of the
        window in which a device ran nothing, each named by the benchmark
        span the host was in at the gap's middle (``other`` if none)."""
        gaps = []
        w0, w1 = self.window
        for d in self.ops:
            t = w0
            for s, e in union((s, e) for _, s, e in self.clipped(d)):
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if t < w1:
                gaps.append((t, w1))
        inner = [sp for sp in self.spans if sp[0] != "window"]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            mid = (s + e) / 2
            name = next((n for n, a, b in inner if a <= mid < b), "other")
            out.append([name, (e - s) / 1e9])
        return out


def leaves(events):
    """The events that hold no other event of the list."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    out = []
    for j, (n, s, e) in enumerate(evs):
        if not (j + 1 < len(evs) and evs[j + 1][1] < e
                and evs[j + 1][2] <= e):
            out.append((n, s, e))
    return out


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _op_line(plane):
    lines = [ln for ln in plane.lines if ln.name not in _SUMMARY_LINES]
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    return (named or sorted(lines, key=lambda ln: -len(list(ln.events))))[0] \
        if lines else None


def load(path: str) -> Trace:
    """Read the trace at ``path`` (an ``.xplane.pb`` file, or a directory
    that holds one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise FileNotFoundError(f"{len(files)} xplane files under {path}")
        path = files[0]
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    spans, ops = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            line = _op_line(plane)
            if line is not None:
                ops[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name in HOST_SPANS]
    windows = [s for s in spans if s[0] == "window"]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} 'window' spans")
    if not ops:
        raise ValueError("trace holds no device plane")
    return Trace(windows[0][1:], spans, ops)
