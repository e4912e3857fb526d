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
    assert spec.dims(spec.load(w["config"], base), base).n_layers == 2
    assert harness.traffic(w["traffic"], base)["lr"] == 0.01
    assert harness.limits("toy-cell", base) == {"loss": 1.0}
    names = [m["name"] for m in harness.per_layer(b, "toy-cell")]
    assert "toy.steps" in names
    assert harness.reader("toy.steps", base)({"steps": 9}) == 9
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("arch", [None, "no-such-arch"],
                         ids=["missing", "unknown"])
def test_a_config_names_an_architecture_that_exists(arch, tmp_path):
    cfg = {k: v for k, v in spec.load("qwen3-0.6b").items()
           if k != "architecture"}
    if arch:
        cfg["architecture"] = arch
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "toy.json"
    path.write_text(json.dumps(dict(cfg, name="toy")))
    with pytest.raises(ValueError, match=str(path)):
        spec.load("toy", str(tmp_path))


# A decoder variant that bench/arch/decoder.py cannot express: attention
# sees only the last ``window`` tokens (the program's static
# ``sliding_window``).  Its reference reaches back ``{reach}`` tokens.
TOY_ARCH = textwrap.dedent('''
    import dataclasses
    import math

    import jax
    import jax.numpy as jnp

    from bench.lib import spec
    from bench.lib.reference import HIGHEST, mm, next_token_loss, rms, rope

    decoder = spec.architecture("decoder")
    PATHS, LAYERED, F32 = decoder.PATHS, decoder.LAYERED, decoder.F32
    shapes, embed, head = decoder.shapes, decoder.embed, decoder.head
    active_params = decoder.active_params
    matmul_params_per_token = decoder.matmul_params_per_token
    attention_flops = decoder.attention_flops   # as if it saw every key


    @dataclasses.dataclass(frozen=True)
    class Dims(decoder.Dims):
        window: int = 0


    def dims(spec):
        return Dims(**dataclasses.asdict(decoder.dims(spec)),
                    window=spec["sliding_window"])


    def program_config(dm, name, **kw):
        return dataclasses.replace(decoder.program_config(dm, name, **kw),
                                   sliding_window=dm.window)


    def hidden(w, tokens, dm, mode="f32", *, lo=0, hi=None, x=None,
               pos=None):
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        if x is None:
            x = embed(w, tokens, dm)
        stack = {{k: w[k][lo:hi].astype(jnp.float32) for k in LAYERED
                 if k in w}}
        H, Kv, hd = dm.n_heads, dm.n_kv, dm.head_dim
        i, j = pos[:, None, :, None], pos[:, None, None, :]
        seen = (i >= j) & (j > i - {reach})

        def layer(x, lw):
            B, S, _ = x.shape
            h = rms(x, lw["attn_norm"], dm.eps)
            q = mm("bsd,dh->bsh", h, lw["wq"], mode).reshape(B, S, H, hd)
            k = mm("bsd,dh->bsh", h, lw["wk"], mode).reshape(B, S, Kv, hd)
            v = mm("bsd,dh->bsh", h, lw["wv"], mode).reshape(B, S, Kv, hd)
            q, k = rms(q, lw["q_norm"], dm.eps), rms(k, lw["k_norm"], dm.eps)
            q, k = rope(q, pos, dm.rope_theta), rope(k, pos, dm.rope_theta)
            k, v = jnp.repeat(k, H // Kv, 2), jnp.repeat(v, H // Kv, 2)
            s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST)
            p = jax.nn.softmax(jnp.where(seen, s / math.sqrt(hd), -jnp.inf),
                               -1)
            o = jnp.einsum("bhst,bthd->bshd", p, v, precision=HIGHEST)
            x = x + mm("bsh,hd->bsd", o.reshape(B, S, H * hd), lw["wo"], mode)
            h = rms(x, lw["mlp_norm"], dm.eps)
            g = mm("bsd,df->bsf", h, lw["w_gate"], mode)
            u = mm("bsd,df->bsf", h, lw["w_up"], mode)
            return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"],
                          mode), None

        x, _ = jax.lax.scan(layer, x, stack)
        return x


    def loss(w, tokens, dm, mode="f32", fault=None):
        return next_token_loss(head(w, hidden(w, tokens, dm, mode), dm, mode),
                               tokens, fault)
''')

TOY_RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from bench.tests.benchtest import run_cell, tiny
    print(json.dumps([
        run_cell("toy-train", root={root!r}),
        run_cell("toy-serve", root={root!r},
                 dims=tiny("toy-window", n_layers=4, tied=False))]))
""")


@pytest.mark.parametrize("reach,correct", [("dm.window", True),
                                           ("2 ** 30", False)],
                         ids=["reference-agrees", "reference-sees-all"])
def test_a_new_architecture_needs_only_new_files_and_entries(
        reach, correct, tmp_path):
    """An architecture, a configuration naming it, a traffic file, limits
    and two cells, added as new files and entries: the training and the
    serving rehearsal run them at tiny widths through the copy's own
    harness, and no file that was there changes.  Where the toy's
    reference ignores the window, as the decoder's would, both cells read
    ``correct`` false: the check used the toy's reference."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _digest(tmp_path / "bench")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    train, serve = (
        next(w for w in bench["workloads"]
             if harness.traffic(w["traffic"])["driver"] == d)
        for d in ("train_driver", "serve_driver"))

    (tmp_path / "bench/arch/toy.py").write_text(TOY_ARCH.format(reach=reach))
    (tmp_path / "bench/configs/toy-window.json").write_text(json.dumps(dict(
        spec.load(train["config"]), name="toy-window", architecture="toy",
        sliding_window=4)))
    (tmp_path / "bench/traffic/toy-chat.json").write_text(json.dumps(
        harness.traffic(serve["traffic"])))
    (tmp_path / "bench/limits/toy-train.json").write_text(json.dumps(
        harness.limits(train["name"])))
    (tmp_path / "bench/limits/toy-serve.json").write_text(
        json.dumps({"served_gap": 0.2}))
    bench["configs"].append({"name": "toy-window", "source": "x",
                             "file": "bench/configs/toy-window.json",
                             "reduced": [], "why": "toy"})
    for name, traffic, like in (("toy-train", train["traffic"], train),
                                ("toy-serve", "toy-chat", serve)):
        bench["workloads"].append({"name": name, "config": "toy-window",
                                   "traffic": traffic, "chips": 1,
                                   "why": "toy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like["name"] in m.get("workloads", []):
                m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    script = tmp_path / "toy.py"
    script.write_text(TOY_RUN.format(root=str(tmp_path),
                                     src=os.path.join(ROOT, "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    for res in json.loads(r.stdout.splitlines()[-1]):
        assert res["correct"] is correct, res["checks"]
        assert res["attempted"] > 0 and res["failed"] == 0
    assert _digest(tmp_path / "bench").items() >= before.items()


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
