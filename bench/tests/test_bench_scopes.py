"""The program's names in a profiler trace: device scopes, executables and
the serving engine's host spans, and the per-layer numbers they give."""
import copy
import gzip
import json
import os

import jax
import pytest

from benchtest import BENCH
from bench.lib import scopes, trace

FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DEV = "/device:TPU:0"


def _load(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        return json.load(f)


def _train():
    # window 0..1000: two train steps 0-400 and 500-900; ops by phase
    ops = [("fusion.1 fusion", 0, 100,
            "jit(train_step)/vmap(jvp(forward))/while/body/closed_call/"
            "attention/dot_general"),
           ("fusion.2 fusion", 100, 200,
            "jit(train_step)/vmap(transpose(jvp(forward)))/while/body/"
            "closed_call/checkpoint/rematted_computation/attention/tanh"),
           ("bitcast_add_fusion.1 fusion", 300, 80,
            "jit(train_step)/optimizer/add"),
           ("copy.3 copy", 380, 20, ""),
           ("while.4 while", 0, 300, ""),
           ("fusion.1 fusion", 500, 100,
            "jit(train_step)/vmap(jvp(forward))/lm_head/dot_general"),
           ("fusion.2 fusion", 600, 200,
            "jit(train_step)/vmap(transpose(jvp(forward)))/mul"),
           ("bitcast_add_fusion.1 fusion", 800, 80,
            "jit(train_step)/optimizer/add"),
           ("copy.3 copy", 880, 20, "")]
    return {"devices": {DEV: [[n, s, d] for n, s, d, _ in ops]},
            "scopes": {DEV: [p for *_, p in ops]},
            "modules": {DEV: [["jit_train_step", 0, 400],
                              ["jit_train_step", 500, 400]]},
            "host": [["bench.window", 0, 1000]]}


def _serve():
    # window 0..1000: decode runs 100-300 and 600-800 (moe 120 of each
    # 200), a prefill 350-550; engine steps 50-450 (fetch 120-320) and
    # 500-950 (fetch 620-920), each inside a bench.engine_step
    def run(t0):
        return [("fusion.5 fusion", t0, 40, "jit(serve_decode)/while/body/"
                 "closed_call/attention/dot_general"),
                ("fusion.6 fusion", t0 + 40, 10, "jit(serve_decode)/while/"
                 "body/closed_call/attention/kv_write/scatter"),
                ("fusion.7 fusion", t0 + 50, 120, "jit(serve_decode)/while/"
                 "body/moe/while/body/dot_general"),
                ("copy.8 copy", t0 + 170, 30, "")]
    ops = run(100) + [("fusion.9 fusion", 350, 200, "jit(serve_prefill)/"
                       "while/body/closed_call/moe/dot_general")] + run(600)
    host = [["bench.window", 0, 1000],
            ["bench.engine_step", 40, 420], ["repro.serve.step", 50, 400],
            ["repro.serve.decode.fetch", 120, 200],
            ["bench.engine_step", 490, 470], ["repro.serve.step", 500, 450],
            ["repro.serve.decode.fetch", 620, 300],
            ["repro.serve.sample", 925, 20]]
    return {"devices": {DEV: [[n, s, d] for n, s, d, _ in ops]},
            "scopes": {DEV: [p for *_, p in ops]},
            "modules": {DEV: [["jit_serve_decode", 100, 200],
                              ["jit_serve_prefill", 350, 200],
                              ["jit_serve_decode", 600, 200]]},
            "host": host}


@pytest.mark.parametrize("path,scope,phase", [
    ("jit(train_step)/vmap(jvp(forward))/while/body/closed_call/attention/"
     "dot_general", "attention", "forward"),
    ("jit(train_step)/vmap(transpose(jvp(forward)))/lm_head/mul",
     "lm_head", "backward"),
    ("jit(train_step)/optimizer/add", "optimizer", "optimizer"),
    ("jit(train_step)/optimizer/shard_map/gossip/permute/ppermute",
     "gossip/permute", "optimizer"),
    ("jit(serve_decode)/while/body/closed_call/attention/kv_write/scatter",
     "kv_write", None),
    ("jit(serve_decode)/layers/while/body/squeeze", "layers", None),
    ("jit(serve_decode)/while/body/squeeze", "", None),
    ("jit(forwarder)/moe_like/add", "", None),
    ("a[0]['layers']['attn']['wq']", "", None),
    ("", "", None),
])
def test_scope_and_phase_of_a_path(path, scope, phase):
    assert scopes.scope_of(path) == scope
    assert scopes.train_phase(path) == phase


def test_train_phases_per_step():
    r = scopes.readings(_train())
    # per step: forward 100, backward 200, optimizer 80 ns
    assert r == pytest.approx({"train.forward_ms": 100e-6,
                               "train.backward_ms": 200e-6,
                               "train.optimizer_ms": 80e-6})


def test_serving_readings():
    r = scopes.readings(_serve(), {"prefill_tokens": 60,
                                   "prefill_slots": 128})
    assert r["serve.decode_ms"] == pytest.approx(200e-6)
    assert r["serve.expert_share"] == pytest.approx(60.0)
    # steps of 400 and 450 less fetches of 200 and 300
    assert r["serve.host_ms_per_step"] == pytest.approx(175e-6)
    assert r["serve.prefill_pad_share"] == pytest.approx(100 * 68 / 128)
    assert not any(k.startswith("train.") for k in r)


def test_gaps_prefer_an_inner_program_span():
    s = trace.reduce(_serve())
    names = {w for w, _ in s["idle_gaps"]}
    assert "bench.engine_step" not in names
    # the middles of 0-100, 300-350 and 550-600 fall in a step outside its
    # fetch; of 800-1000, in the second fetch
    assert names == {"repro.serve.step", "repro.serve.decode.fetch"}


def test_coverage_and_unscoped_ops():
    c = scopes.coverage(_serve())
    assert c["scopes"] == pytest.approx({
        "attention": 80e-9, "kv_write": 20e-9, "moe": 440e-9, "": 60e-9})
    assert c["modules"] == pytest.approx({
        "jit_serve_decode": 400e-9, "jit_serve_prefill": 200e-9})
    assert scopes.unscoped_ops(_serve()) == [["copy.8 copy", "",
                                              pytest.approx(60e-9)]]


@pytest.mark.parametrize("raw", [{}, {"devices": {}, "host": []}],
                         ids=["empty", "no-window"])
def test_nothing_to_read_gives_nothing(raw):
    assert scopes.readings(raw) == {}


def test_a_trace_without_program_names_reads_nothing():
    """The committed training trace (a program without scopes, spans or
    executables named): nothing to read, and ``reduce`` gives what it gave
    before, field for field, with the new keys beside it."""
    raw = _load("v5e_train_1node.json.gz")
    assert scopes.readings(raw) == {}
    wider = dict(copy.deepcopy(raw), scopes={DEV: []}, modules={DEV: []})
    assert trace.reduce(wider) == trace.reduce(raw)


def test_extract_keeps_program_spans_beside_the_benchmarks(tmp_path):
    """A CPU profiler session (no device plane): ``extract`` keeps the
    ``bench.*`` and ``repro.*`` host spans; dropping the program's gives
    ``trace.extract``'s list."""
    f = jax.jit(lambda x: x * 2)
    f(1.0)
    jax.profiler.start_trace(str(tmp_path))
    with trace.span("bench.window", True):
        with jax.profiler.TraceAnnotation("repro.serve.step"):
            f(2.0).block_until_ready()
    jax.profiler.stop_trace()
    raw = scopes.extract(str(tmp_path))
    assert [n for n, _, _ in raw["host"]] == ["bench.window",
                                              "repro.serve.step"]
    old = trace.extract(str(tmp_path))
    assert old["devices"] == raw["devices"] == {}
    assert old["host"] == [h for h in raw["host"]
                           if h[0].startswith(trace.SPAN_PREFIX)]


def test_recorded_serving_trace():
    """About a second of the serving cell's traced window on a TPU v5e,
    extracted by ``extract``: every reading is there and in range, the
    program's scopes name most of the busy time, and every idle gap is
    named by one of the engine's spans, none by ``bench.engine_step``."""
    raw = _load("v5e_serve_overload.json.gz")
    r = scopes.readings(raw)
    assert set(r) == {"serve.decode_ms", "serve.expert_share",
                      "serve.host_ms_per_step"}
    assert 20 < r["serve.decode_ms"] < 200
    assert 0 < r["serve.expert_share"] < 100
    assert 0 < r["serve.host_ms_per_step"] < r["serve.decode_ms"]
    s = trace.reduce(raw)
    c = scopes.coverage(raw)
    assert c["scopes"].get("", 0.0) < 0.15 * s["busy_s"]
    assert {"jit_serve_decode", "jit_serve_prefill"} <= set(c["modules"])
    assert s["idle_gaps"] and all(w.startswith("repro.serve.")
                                  for w, _ in s["idle_gaps"])
