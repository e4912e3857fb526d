"""Read the limits' upper ends: the reference in the program's place,
computed in the control precision or with a planted fault, against the
plain reference, at a cell's own sizes.

    python bench/control.py --workload <cell> --seeds 11 12 13

Prints one JSON line per seed and variant with the compared numbers.

Training cells: ``fp8`` (the control: weight products in
float8_e4m3fn, the precision below the bfloat16 the configuration
computes in), ``half`` (the loss over half of each row), ``no_exchange``
(no gossip between nodes; four-node cells only).  A state left unchanged
reads 1 on ``change`` by construction and needs no run.

Serving cells: a short window at the cell's own load serves requests as
a run does; then, over the same seeded sample of finished requests, the
widest gap below the reference's best of the token the program served
(``program``), of the token the control (fp8 weight products) puts first
(``fp8``), and of each served token altered by one (``altered``).

The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def readings(workload: str, seeds, *, require_tpu: bool = True, dims=None,
             variants=None):
    import jax
    import numpy as np

    from bench.lib import bigram, compare, harness, spec, train_driver, \
        weights

    w = harness.cell(harness.benchmark(), workload)
    mix = harness.traffic(w["traffic"])
    devs = harness.devices(w["chips"], require_tpu=require_tpu)
    dm = dims or spec.dims(spec.load(w["config"]))
    n = mix["nodes"]
    variants = variants or (["fp8", "half"]
                            + (["no_exchange"] if n > 1 else []))
    for seed in seeds:
        wkey = weights.key(seed)
        toks = np.asarray(bigram.batches(
            jax.random.key(weights.seed32(seed, salt=2)), vocab=dm.vocab,
            n_nodes=n, n_batches=train_driver.FIRST_STEPS,
            batch=mix["per_node_batch"], seq=mix["seq"],
            hetero=mix["hetero"]))

        def obs(mode="f32", fault=None):
            return train_driver._reference_observations(
                dm, devs, n, toks, mix["lr"], mix["beta"], wkey, mode=mode,
                fault=fault)

        t0 = time.perf_counter()
        ref = obs()
        for v in variants:
            got = obs(mode="fp8") if v == "fp8" else obs(fault=v)
            yield {"seed": seed, "variant": v,
                   "numbers": compare.numbers(got, ref),
                   "still_leaves": compare.still_leaves(ref),
                   "seconds": time.perf_counter() - t0}


def serve_readings(workload: str, seeds, *, seconds: float = 20.0,
                   require_tpu: bool = True, dims=None, mix=None):
    import time as _time

    import numpy as np

    from bench.lib import harness, serve_driver, spec, weights

    w = harness.cell(harness.benchmark(), workload)
    mix = mix or harness.traffic(w["traffic"])
    devs = harness.devices(w["chips"], require_tpu=require_tpu)
    dm = dims or spec.dims(spec.load(w["config"]))
    for seed in seeds:
        out = serve_driver.run(workload=w, config=w["config"], traffic=mix,
                               limits=harness.limits(w["name"]), devs=devs,
                               seed=seed, seconds=seconds, trace=False,
                               t_start=_time.perf_counter(), dims=dims,
                               keep_sample=True)
        sample = out["sample"]
        wkey = weights.key(seed)
        ref = serve_driver.Judge(dm, wkey, pad=mix["max_seq"])
        ctl = serve_driver.Judge(dm, wkey, pad=mix["max_seq"], mode="fp8")
        picks = serve_driver.first_choices(ctl, sample)
        ctl_sample = [(np.concatenate([s[:p], c.astype(np.int32)]), p)
                      for (s, p), c in zip(sample, picks)]
        alt_sample = [(np.concatenate([s[:p], (s[p:] + 1) % dm.vocab]), p)
                      for s, p in sample]
        for name, smp in (("program", sample), ("fp8", ctl_sample),
                          ("altered", alt_sample)):
            gaps = serve_driver.served_gaps(ref, smp)
            yield {"seed": seed, "variant": name,
                   "numbers": {"served_gap": float(max(g.max()
                                                       for g in gaps))},
                   "tokens": int(sum(len(g) for g in gaps))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.lib import harness

    driver = harness.traffic(harness.cell(harness.benchmark(),
                                          args.workload)["traffic"])["driver"]
    fn = serve_readings if driver == "serve_driver" else readings
    try:
        for r in fn(args.workload, args.seeds):
            print(json.dumps(r), flush=True)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
