"""Each cell end to end on the CPU at tiny widths, through the
same harness and driver as on the chip (the four-node cell on four
virtual devices, the combine kernel in interpret mode).  No number from
these runs is a device metric; they show the generator, the window and the
comparison."""
import json
import subprocess
import sys
import textwrap

from benchtest import ROOT, SERVE, TINY_LIMITS, TRAIN_1, TRAIN_4, \
    run_cell, subprocess_env
from bench.lib import harness


def _check_result(res, workload, root=ROOT):
    e2e = {m["name"] for m in harness.end_to_end(harness.benchmark(root),
                                                 workload)}
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(TINY_LIMITS.get(workload)
                                     or harness.limits(workload))


def test_one_node_cell():
    res = run_cell(TRAIN_1)
    _check_result(res, TRAIN_1)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 1


def test_serving_cell():
    res = run_cell(SERVE)
    _check_result(res, SERVE)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] >= 0


FOUR = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}]
    from bench.tests.benchtest import run_cell, write_root
    print(json.dumps(run_cell({workload!r}, root=write_root({tmp!r}))))
""")


def test_four_node_cell_on_four_devices(tmp_path):
    script = tmp_path / "four.py"
    script.write_text(FOUR.format(root=ROOT, workload=TRAIN_4,
                                  tmp=str(tmp_path)))
    r = subprocess.run([sys.executable, str(script)], env=subprocess_env(4),
                       cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.splitlines()[-1])
    _check_result(res, TRAIN_4, root=str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["checks"]["consensus"]["value"] < 1e-6
