"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic and
limits are found by name (see ``bench/lib/harness.py``).  Set-up makes the
weights and the inputs on the device from ``--seed``, warms every program
the window uses (JAX's persistent cache lives in ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` is set), then measures for
``--seconds``; ``--trace 1`` reports the per-layer metrics from a profiled
window instead of the end-to-end ones.  The last line of stdout is one JSON
object; the compared numbers and their limits end stderr.  Without a TPU,
or with fewer chips than the cell needs, it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness

    try:
        harness.devices(harness.cell(harness.benchmark(), args.workload)
                        ["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    from repro.core.cache import enable_persistent_cache

    import jax

    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = harness.execute(args, t_start=T_START)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
