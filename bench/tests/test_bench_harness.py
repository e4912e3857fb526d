"""The harness finds every piece of a cell by name, a new cell needs only
new files and entries, and the command refuses to run without a TPU."""
import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchtest import BENCH, ROOT
from bench.lib import harness, spec


def _bench():
    return harness.benchmark()


def test_every_cell_resolves_by_name():
    bench = _bench()
    for w in bench["workloads"]:
        cfg = spec.load(w["config"])
        assert cfg["name"] == w["config"]
        mix = harness.traffic(w["traffic"])
        assert importlib.util.find_spec(harness.driver_module(mix))
        limits = harness.limits(w["name"])
        assert limits and all(v > 0 for v in limits.values())
        assert harness.end_to_end(bench, w["name"])
        layer = harness.per_layer(bench, w["name"])
        assert layer
        for m in layer:
            assert callable(harness.reader(m["name"]))


def test_config_files_match_benchmark_entries():
    for c in _bench()["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        data = spec.load(c["name"])
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_readers_find_nothing_in_an_empty_context():
    for m in _bench()["per_layer"]:
        assert harness.reader(m["name"])({}) is None


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _digest(tmp_path / "bench")
    base = str(tmp_path / "bench")

    cfg = dict(spec.load("qwen3-0.6b"), name="toy-dense",
               num_hidden_layers=2)
    (tmp_path / "bench/configs/toy-dense.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/toy-mix.json").write_text(json.dumps(
        dict(harness.traffic("dmsgd-1node"), lr=0.01)))
    (tmp_path / "bench/limits/toy-cell.json").write_text(
        json.dumps({"loss": 1.0}))
    (tmp_path / "bench/metrics/toy.steps.py").write_text(
        "def read(ctx):\n    return ctx.get('steps')\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-dense", "source": "x",
                             "file": "bench/configs/toy-dense.json",
                             "reduced": ["num_hidden_layers"], "why": "toy"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy-dense",
                               "traffic": "toy-mix", "chips": 1,
                               "why": "toy"})
    e2e = bench["end_to_end"][0]["name"]
    bench["end_to_end"][0]["workloads"].append("toy-cell")
    bench["per_layer"].append({"name": "toy.steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "toy", "moves": e2e,
                               "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    b = harness.benchmark(str(tmp_path))
    w = harness.cell(b, "toy-cell")
    assert spec.dims(spec.load(w["config"], base)).n_layers == 2
    assert harness.traffic(w["traffic"], base)["lr"] == 0.01
    assert harness.limits("toy-cell", base) == {"loss": 1.0}
    names = [m["name"] for m in harness.per_layer(b, "toy-cell")]
    assert "toy.steps" in names
    assert harness.reader("toy.steps", base)({"steps": 9}) == 9
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train-qwen3-0.6b-1node", "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_command_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "needs a TPU" in r.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert _no_result(r.stdout)


@pytest.mark.parametrize("more", [1, 3])
def test_too_few_chips_is_refused(more):
    import jax

    with pytest.raises(harness.NoChip):
        harness.devices(len(jax.devices()) + more, require_tpu=False)


BURSTY = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from bench.tests.benchtest import run_cell
    print(json.dumps(run_cell("toy-bursty-cell", root={root!r})))
""")


def test_a_bursty_mix_needs_only_new_files_and_entries(tmp_path):
    """A mix with on/off arrivals, added as data: a traffic file, a limits
    file and entries in BENCHMARK.json; it runs end to end at tiny widths
    through the copy's own harness, and no file that was there changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _digest(tmp_path / "bench")
    serve = next(w for w in _bench()["workloads"]
                 if harness.traffic(w["traffic"])["driver"] == "serve_driver")
    mix = dict(harness.traffic(serve["traffic"]), backlog=0,
               await_first_tokens=True,
               arrivals={"law": "onoff", "rate": 2.0, "on_s": 0.5,
                         "off_s": 0.5})
    (tmp_path / "bench/traffic/toy-bursty.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/toy-bursty-cell.json").write_text(
        json.dumps({"served_gap": 0.2}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy-bursty-cell",
                               "config": serve["config"],
                               "traffic": "toy-bursty", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if serve["name"] in m.get("workloads", []):
            m["workloads"].append("toy-bursty-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = tmp_path / "bursty.py"
    script.write_text(BURSTY.format(root=str(tmp_path),
                                    src=os.path.join(ROOT, "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["serve_out_tok_s"]["value"] > 0
    assert _digest(tmp_path / "bench").items() >= before.items()
