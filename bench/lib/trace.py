"""From a profiler trace to device busy time, op sums and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (kept small enough to commit as a test fixture):

    {"devices": {plane: [[op name, start ns, duration ns], ...]},
     "host":    [[span name, start ns, duration ns], ...]}

``devices`` holds each TPU's "XLA Ops" line, each op named by ``label``
(the trace names an op by its whole HLO instruction); ``host`` holds the
benchmark's own spans (``bench.*`` trace annotations).  All times share
the trace's clock.  ``reduce`` turns that into the numbers the per-layer
readers and the ``breakdown`` use, over the window that the ``bench.window``
span marks.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
CUSTOM_TEXT = 4000  # characters of a custom call's text kept in its label
NAME_CHARS = 160    # characters of a label shown in the breakdown
# ops that contain others (a loop, a branch): they count as busy, but their
# time is the time of the ops inside, which are summed instead
CONTAINERS = ("while", "conditional", "call")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def label(text: str) -> str:
    """``"<instruction> <opcode>"`` from an op's HLO text, e.g.
    ``"fusion.12 fusion"``; a custom call keeps its target and the start of
    its configuration, where a kernel's name is."""
    m = _INSTR.match(text)
    if not m:
        return text[:CUSTOM_TEXT]
    rest = text[m.end():]
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else "?"
    out = f"{m.group(1)} {opcode}"
    if opcode == "custom-call":
        tail = rest[op.end():]
        at = tail.find("custom_call_target=")
        out += " " + tail[max(at, 0):][:CUSTOM_TEXT]
    return out


def span(name: str, on: bool):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend([label(e.name), float(e.start_ns),
                                float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def window(raw: dict) -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in raw["host"] if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(raw: dict, *, top: int = 10) -> dict:
    """busy_s, window_s (averaged over devices), per-op device seconds
    summed over devices, and the longest idle gaps named by the host span
    that covers each gap's middle (innermost span wins)."""
    lo, hi = window(raw)
    devices = sorted(raw["devices"])
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy = []
    op_s: collections.Counter = collections.Counter()
    gaps = []
    spans = sorted(((s, s + d, n) for n, s, d in raw["host"]
                    if n != WINDOW), key=lambda t: t[1] - t[0])
    for dev in devices:
        ops = raw["devices"][dev]
        merged = _union(_clip([[s, s + d] for _, s, d in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in ops:
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0 and name.split(" ")[1:2] not in [[c] for c in
                                                         CONTAINERS]:
                op_s[name] += inside
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                what = next((n for s, e, n in spans if s <= mid <= e),
                            "no bench span")
                gaps.append((b - a, what))
    n = len(devices)
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": n,
        "op_s": {k: v * 1e-9 for k, v in op_s.items()},
        "device_ops": [[k[:NAME_CHARS], v * 1e-9 / n]
                       for k, v in op_s.most_common(top)],
        "idle_gaps": [[w, g * 1e-9] for g, w in gaps[:top]],
    }


def op_seconds(summary: dict, pattern: str) -> float | None:
    """Device seconds of the ops whose name matches ``pattern``, summed
    over devices and averaged per device; None where no op matches."""
    rx = re.compile(pattern)
    hits = [v for k, v in summary["op_s"].items() if rx.search(k)]
    if not hits:
        return None
    return sum(hits) / summary["devices"]
