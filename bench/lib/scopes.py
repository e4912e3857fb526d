"""The program's own names in a profiler trace: the device scope of every
op, the executable each op ran in, and the program's host spans.

``extract`` reads what ``trace.extract`` reads, and more:

    {"devices": {plane: [[op label, start ns, duration ns], ...]},
     "host":    [[span name, start ns, duration ns], ...],
     "scopes":  {plane: [scope path of each op of devices[plane]]},
     "modules": {plane: [[executable, start ns, duration ns], ...]}}

``host`` holds the benchmark's ``bench.*`` spans and the program's
``repro.*`` spans (the serving engine's ``repro.serve.*``), so
``trace.reduce`` on this dict names each idle gap by the innermost of
either.  On a trace without program spans ``devices`` and ``host`` are
exactly what ``trace.extract`` gives.

A TPU v5e trace's op events carry no metadata: an "XLA Ops" event is named
by its HLO text and its stats are device offsets.  The scope path is the
``op_name`` metadata of the op's HLO instruction (``jit(serve_decode)/
while/body/closed_call/moe/...``), which the profile keeps with each
executable's HLO; xprof's ``hlo_stats`` reads it, keyed by the executable's
program id (the number in the "XLA Modules" event's name) and the
instruction's name.  Without xprof every scope path is ``""``.

``readings`` turns that into per-layer numbers: device time per step by
train-step phase (``forward``; ``transpose(``, the backward pass with the
remat recompute; ``optimizer``), the serving decode executable's time and
its expert share, and host time per engine step outside the logits fetch.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re

from . import trace

PROGRAM_SPAN_PREFIX = "repro."
MODULE_LINE = "XLA Modules"
_MODULE = re.compile(r"^(.*)\((\d+)\)$")
# the program's device scopes (``scope_of`` names an op by the innermost)
SCOPES = ("forward", "optimizer", "layers", "attention", "moe", "kv_write",
          "lm_head", "gossip/pack", "gossip/permute", "gossip/combine")
TRAIN_STEP, DECODE = "jit_train_step", "jit_serve_decode"
# one component of a scope path: a name, maybe inside transformations,
# e.g. ``vmap(transpose(jvp(forward)))``
_COMPONENT = re.compile(r"(?:\w+\()*(\w+)\)*")


def op_names(path: str) -> dict:
    """``{(program id, instruction name): op_name}`` of every op the
    profile ran, from xprof's ``hlo_stats``; empty where xprof cannot
    read it."""
    try:
        from xprof.convert import raw_to_tool_data

        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [path], "hlo_stats", {"use_saved_result": False})
        table = json.loads(data)
    except Exception:  # noqa: BLE001 - any failure: no scope paths
        return {}
    cols = [c["id"] for c in table["cols"]]
    out = {}
    for row in table["rows"]:
        r = dict(zip(cols, (c.get("v") if c else None for c in row["c"])))
        out[(str(r["program_id"]), r["hlo_op_name"])] = \
            (r["tf_op_name"] or "").rstrip(":")
    return out


def _module(event_name: str) -> tuple:
    """``(executable, program id)`` from an "XLA Modules" event's name,
    e.g. ``jit_serve_decode(3094724454356856304)``."""
    m = _MODULE.match(event_name)
    return (m.group(1), m.group(2)) if m else (event_name, "")


def extract(trace_dir: str) -> dict:
    """The dict of the module docstring from the profile in
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    names = op_names(paths[-1])
    devices, scopes, modules, host = {}, {}, {}, []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            runs = sorted((_module(e.name) + (float(e.start_ns),
                                               float(e.duration_ns))
                           for line in plane.lines
                           if line.name == MODULE_LINE
                           for e in line.events), key=lambda r: r[2])
            starts = [r[2] for r in runs]
            op_paths = scopes.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != trace.OP_LINE:
                    continue
                for e in line.events:
                    ops.append([trace.label(e.name), float(e.start_ns),
                                float(e.duration_ns)])
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    m = trace._INSTR.match(e.name)
                    key = (runs[i][1] if i >= 0 else "",
                           m.group(1) if m else "")
                    op_paths.append(names.get(key, ""))
            modules[plane.name] = [[name, s, d] for name, _, s, d in runs]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith((trace.SPAN_PREFIX,
                                                  PROGRAM_SPAN_PREFIX)))
    return {"devices": devices, "host": host, "scopes": scopes,
            "modules": modules}


def scope_of(path: str) -> str:
    """The innermost of the program's device scopes in an op's scope
    path, or ``""``: ``.../attention/kv_write/scatter`` -> ``kv_write``,
    ``.../vmap(transpose(jvp(forward)))/...`` -> ``forward``."""
    parts = path.split("/")
    found = ""
    for i, part in enumerate(parts):
        m = _COMPONENT.fullmatch(part)
        word = m.group(1) if m else ""
        if word == "gossip" and i + 1 < len(parts):
            word = f"gossip/{parts[i + 1]}"
        if word in SCOPES:
            found = word
    return found


def train_phase(path: str) -> str | None:
    """``forward``, ``backward`` (under ``transpose(``, remat recompute
    included: it is named ``transpose(jvp(forward))/.../rematted_
    computation/...``) or ``optimizer``; None for an op in none."""
    if "transpose(" in path:
        return "backward"
    if re.search(r"(^|[/(])forward([/)]|$)", path):
        return "forward"
    if re.search(r"(^|/)optimizer(/|$)", path):
        return "optimizer"
    return None


def module_runs(raw: dict, module: str) -> dict:
    """``{plane: [(start, end) ns]}`` of the runs of executable ``module``
    that start inside the window."""
    lo, hi = trace.window(raw)
    return {dev: [(s, s + d) for name, s, d in runs
                  if name == module and lo <= s < hi]
            for dev, runs in raw.get("modules", {}).items()}


def _ops(raw: dict, module: str | None = None):
    """``(device, label, scope path, ns inside the window)`` of each op in
    the window, loops and calls left out as in ``trace.reduce``;
    optionally only the ops inside runs of executable ``module``."""
    lo, hi = trace.window(raw)
    runs = module_runs(raw, module) if module else {}
    for dev in sorted(raw["devices"]):
        paths = raw.get("scopes", {}).get(dev) or []
        starts = [a for a, _ in runs.get(dev, [])]
        for i, (name, s, d) in enumerate(raw["devices"][dev]):
            inside = min(s + d, hi) - max(s, lo)
            if inside <= 0 or name.split(" ")[1:2] in [
                    [c] for c in trace.CONTAINERS]:
                continue
            if module:
                j = bisect.bisect_right(starts, s) - 1
                if j < 0 or s >= runs[dev][j][1]:
                    continue
            yield dev, name, paths[i] if i < len(paths) else "", inside


def device_seconds(raw: dict, key, *, module: str | None = None) -> dict:
    """Device seconds per device of the window's ops summed by
    ``key(scope path)`` (ops whose key is None left out), optionally only
    the ops inside runs of executable ``module``."""
    out: collections.Counter = collections.Counter()
    for _, _, path, inside in _ops(raw, module):
        k = key(path)
        if k is not None:
            out[k] += inside
    return {k: v * 1e-9 / len(raw["devices"]) for k, v in out.items()}


def host_ms_per_step(raw: dict) -> float | None:
    """Mean over the window's ``repro.serve.step`` spans of their length
    less the time inside their ``repro.serve.*.fetch`` spans: the host's
    own work in a step, while the device may wait for it."""
    lo, hi = trace.window(raw)
    steps = [(s, s + d) for n, s, d in raw["host"]
             if n == "repro.serve.step" and lo <= s and s + d <= hi]
    if not steps:
        return None
    fetch = [(s, s + d) for n, s, d in raw["host"]
             if n.startswith("repro.serve.") and n.endswith(".fetch")]
    own = []
    for a, b in steps:
        waited = sum(max(0.0, min(e, b) - max(s, a)) for s, e in fetch)
        own.append(b - a - waited)
    return sum(own) / len(own) * 1e-6


def _under(scope: str):
    """A ``device_seconds`` key: ``scope`` for a path with a ``scope``
    component, else None."""
    rx = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    return lambda path: scope if rx.search(path) else None


def readings(raw: dict, counters: dict | None = None) -> dict:
    """The per-layer numbers the program's names allow, by metric name; a
    number is left out where the trace has nothing to read (a trace of a
    program without names gives ``{}``).  ``counters`` holds the window's
    ``prefill_tokens`` and ``prefill_slots`` (the engine's counters read
    at both ends of the window)."""
    out = {}
    if counters and counters.get("prefill_slots"):
        slots, tokens = counters["prefill_slots"], counters["prefill_tokens"]
        out["serve.prefill_pad_share"] = 100.0 * (slots - tokens) / slots
    if not raw.get("devices") or not any(
            n == trace.WINDOW for n, _, _ in raw.get("host", [])):
        return out
    n_dev = len(raw["devices"])
    steps = sum(len(r) for r in module_runs(raw, TRAIN_STEP).values())
    if steps:
        phases = device_seconds(raw, train_phase, module=TRAIN_STEP)
        for phase in ("forward", "backward", "optimizer"):
            if phase in phases:
                out[f"train.{phase}_ms"] = 1e3 * phases[phase] * n_dev / steps
    runs = [b - a for r in module_runs(raw, DECODE).values() for a, b in r]
    if runs:
        out["serve.decode_ms"] = sum(runs) / len(runs) * 1e-6
        moe = device_seconds(raw, _under("moe"), module=DECODE).get("moe")
        if moe is not None:
            out["serve.expert_share"] = (100.0 * moe * n_dev
                                         / (sum(runs) * 1e-9))
    host = host_ms_per_step(raw)
    if host is not None:
        out["serve.host_ms_per_step"] = host
    return out


def coverage(raw: dict) -> dict:
    """Device seconds per device in the window by the innermost program
    scope of each op (``""``: under none), and by executable."""
    lo, hi = trace.window(raw)
    by_module: collections.Counter = collections.Counter()
    for runs in raw.get("modules", {}).values():
        for name, s, d in runs:
            by_module[name] += max(0.0, min(s + d, hi) - max(s, lo))
    n = max(len(raw.get("modules", {})), 1)
    return {"scopes": device_seconds(raw, scope_of),
            "modules": {k: v * 1e-9 / n for k, v in by_module.items()}}


def unscoped_ops(raw: dict, top: int = 10) -> list:
    """The ``top`` ops under no program scope, by device seconds per
    device in the window: ``[[label, scope path, seconds], ...]``."""
    out: collections.Counter = collections.Counter()
    for _, name, path, inside in _ops(raw):
        if not scope_of(path):
            out[(name[:trace.NAME_CHARS], path)] += inside
    n = len(raw["devices"])
    return [[k[0], k[1], v * 1e-9 / n] for k, v in out.most_common(top)]
