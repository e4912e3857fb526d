"""Helpers the benchmark's CPU tests share (tiny sizes only)."""
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import harness, spec  # noqa: E402,F401

TRAIN_4 = "train-qwen3-0.6b-dmsgd-1peer-4chip"
TRAIN_1 = "train-qwen3-0.6b-1node"
SERVE = "serve-granite-moe-3b-overload"


def tiny(config: str = "qwen3-0.6b", **kw):
    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16,
                d_ff=128, vocab=256)
    if config == "granite-moe-3b-a800m":
        # the reference runs four layers at a time; untied, because at
        # these widths a tied head mostly repeats the current token, which
        # no fault in attention or the cache would change.  Every token is
        # routed to all four experts: with two of four, a router near-tie
        # that float32 and bfloat16 break apart swaps an expert that
        # weighs about a third of the output, and one token of a few
        # hundred reads a gap of 0.2 to 1.9 on a sound run (top-8 of 40 on
        # the chip weighs the marginal expert far less)
        base.update(n_layers=4, d_ff=32, n_experts=4, top_k=4, tied=False)
    base.update(kw)
    return spec.shrink(spec.dims(spec.load(config)), **base)


def tiny_mix(workload: str, root: str = ROOT) -> dict | None:
    """The cell's traffic with lengths, pool and batch cut for the CPU."""
    w = harness.cell(harness.benchmark(root), workload)
    if w["name"] == TRAIN_4:
        return FOUR_NODE_MIX
    mix = harness.traffic(w["traffic"])
    if mix["driver"] != "serve_driver":
        return None
    # answers longer than prompts, so that a fault in decoding weighs
    return dict(mix, arrivals=dict(mix["arrivals"], rate=4.0),
                backlog=min(mix.get("backlog", 0), 6),
                prompt={"law": "lognormal", "median": 8, "sigma": 0.7,
                        "min": 2, "cap": 32},
                output={"law": "lognormal", "median": 24, "sigma": 0.5,
                        "cap": 48},
                max_seq=128, max_batch=4, n_pages=64,
                prefill_token_budget=64, check_requests=3)


# four nodes over the one-peer exponential graph: the training driver's
# path across devices, run on four virtual CPU devices.  No such cell is in
# BENCHMARK.json yet, so its mix and limits live here.
FOUR_NODE_MIX = {"driver": "train_driver", "nodes": 4,
                 "topology": "one_peer_exp", "optimizer": "dmsgd",
                 "beta": 0.9, "lr": 0.002, "per_node_batch": 1, "seq": 512,
                 "n_batches": 16, "hetero": 0.5, "trace_seconds": 4}

# limits for the tiny widths where the chip's do not carry over: a tiny
# model's logits lie on another scale (sound runs read up to about 0.05,
# an altered token or a stale pool well above 1); four nodes take the
# one-node cell's limits and the exact average after log2(4) rounds
TINY_LIMITS = {SERVE: {"served_gap": 0.2},
               TRAIN_4: dict(harness.limits(TRAIN_1), consensus=1e-3)}

FOUR_NODE_CELL = {"name": TRAIN_4, "config": "qwen3-0.6b",
                  "traffic": "four-nodes", "chips": 4,
                  "why": "four nodes, one per device"}


def write_root(path: str) -> str:
    """A checkout root whose BENCHMARK.json also holds a four-node cell."""
    bench = harness.benchmark()
    if all(w["name"] != TRAIN_4 for w in bench["workloads"]):
        bench["workloads"].append(FOUR_NODE_CELL)
        for m in bench["end_to_end"]:
            if TRAIN_1 in m.get("workloads", []):
                m["workloads"].append(TRAIN_4)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return path


def run_cell(workload: str, *, seed: int = 2 ** 33 + 7, seconds=None,
             dims=None, root: str = ROOT) -> dict:
    """One run of a cell on the CPU at tiny widths, as the chip runs it."""
    w = harness.cell(harness.benchmark(root), workload)
    mix = tiny_mix(workload, root)
    if seconds is None:
        seconds = 0.5 if mix is None else 3.0
    args = types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=0)
    return harness.execute(args, t_start=time.perf_counter(), root=root,
                           require_tpu=False,
                           dims=dims or tiny(w["config"]), mix=mix,
                           limit_values=TINY_LIMITS.get(workload))


def subprocess_env(n_devices: int) -> dict:
    """A CPU-only child with ``n_devices`` virtual devices; the gossip
    combine runs its Pallas kernel in interpret mode, as on the chip."""
    return dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_NUM_CPU_DEVICES=str(n_devices),
                REPRO_GOSSIP_PALLAS="interpret",
                PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
