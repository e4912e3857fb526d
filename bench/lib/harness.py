"""Finds a cell's configuration, traffic, limits and per-layer readers by
the names in ``BENCHMARK.json``, runs its driver and prints the result.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own:

    bench/configs/<config>.json     sizes as run; its "architecture"
                                    names the module below
    bench/arch/<architecture>.py    dimensions, weight table, reference
                                    and FLOP counts of a kind of model
                                    (see spec.py)
    bench/traffic/<traffic>.json    parameters of one job or mix; its
                                    "driver" names the module under
                                    bench/lib/ that runs it
    bench/limits/<workload>.json    the limit of each compared number
    bench/metrics/<metric>.py       read(ctx) -> value or None

so a later change adds a cell, a mix, a configuration, an architecture or
a metric by adding files and entries only.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell needs."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def traffic(name: str, base: str = BENCH) -> dict:
    return load_json(base, "traffic", f"{name}.json")


def driver_module(mix: dict) -> str:
    """The module that runs a traffic mix: ``bench/lib/<driver>.py``."""
    return f"bench.lib.{mix['driver']}"


def limits(workload: str, base: str = BENCH) -> dict:
    return load_json(base, "limits", f"{workload}.json")


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def end_to_end(bench: dict, workload: str) -> list:
    return [m for m in bench["end_to_end"] if _applies(m, workload)]


def per_layer(bench: dict, workload: str) -> list:
    e2e = {m["name"] for m in end_to_end(bench, workload)}
    return [m for m in bench["per_layer"]
            if _applies(m, workload) and m["moves"] in e2e]


def reader(metric: str, base: str = BENCH):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(base, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(n: int, *, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX sees {len(devs)}")
    return devs[:n]


def settle_heap() -> None:
    """End of set-up: collect, then move every object set-up made (traced
    programs, executables, array handles) out of the collector's reach.
    They live through the window, and a full collection that scans them
    stalls whichever step it falls in.  ``gc.unfreeze()`` hands them back
    before the program's state is freed for the reference."""
    gc.collect()
    gc.freeze()


def peak_memory(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(args, *, t_start: float, root: str = ROOT,
            require_tpu: bool = True, dims=None, mix=None,
            limit_values=None) -> dict:
    """Run one cell once; returns the result object (not yet printed).
    ``dims``, ``mix`` and ``limit_values`` replace the configuration's
    sizes, the traffic file and the limits: the CPU tests use them at
    their tiny widths, the chip never does."""
    bench = benchmark(root)
    w = cell(bench, args.workload)
    devs = devices(w["chips"], require_tpu=require_tpu)
    mix = mix or traffic(w["traffic"])
    driver = importlib.import_module(driver_module(mix))
    out = driver.run(workload=w, config=w["config"], traffic=mix,
                     limits=limit_values or limits(w["name"]), devs=devs,
                     seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     t_start=t_start, dims=dims)
    if args.trace:
        ctx = out["reader_ctx"]
        metrics = {}
        for m in per_layer(bench, w["name"]):
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in end_to_end(bench, w["name"])}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak"]}
    if args.trace:
        device.update(busy_s=out["trace"]["busy_s"],
                      window_s=out["trace"]["window_s"])
    checks = out["checks"]
    correct = bool(out["ok"]) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    result["checks"] = checks
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}", file=err)
    err.flush()
    print(json.dumps(_finite(result)), file=out, flush=True)


def _finite(x):
    """JSON has no infinities: a number that is not finite is written as
    null (a run that reads one is not correct anyway)."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x
