"""The names the program gives its work: device scopes in the HLO
metadata, executable and kernel names, the serving engine's host spans,
counters and timestamps."""
import contextlib
import glob
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import topology
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod
from repro.models import model as M
from repro.serve import ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, L = 2, 32            # the serving executables' bucket


def _train_step_hlo():
    cfg = configs.reduced_config(configs.get_config("qwen3-0.6b"))
    opt, step_for = train_mod.build_trainer(
        cfg, topology.get_topology("one_peer_exp", 1), "dmsgd", 0.9)
    params = jax.eval_shape(lambda k: jax.tree.map(
        lambda a: a[None], M.init(cfg, k)), jax.random.key(0))
    state = jax.eval_shape(opt.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)}
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    return step_for.plan.lowered(0, params, state, batch,
                                 lr).compile().as_text()


def _engine():
    cfg = configs.reduced_config(configs.get_config("granite-moe-3b-a800m"))
    return ServeEngine(cfg, M.init(cfg, jax.random.key(0)), n_pages=16,
                       page_size=16, max_seq=64, max_batch=B)


def _serve_hlo():
    eng = _engine()
    z = np.zeros((B, L), np.int32)
    prefill = eng._prefill_exe(B, L).lower(
        eng.params, z, z, eng.pool, z, z,
        np.zeros((B,), np.int32)).compile().as_text()
    decode = eng._decode_exe(B).lower(
        eng.params, np.zeros((B, 1), np.int32), eng.pool,
        np.zeros((B, eng.pmax), np.int32),
        np.zeros((B,), np.int32)).compile().as_text()
    return {"serve_prefill": prefill, "serve_decode": decode}


def _build_all():
    return {"train_step": _train_step_hlo(), **_serve_hlo()}


@pytest.fixture(scope="module")
def hlo():
    return _build_all()


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _structure(text):
    """The compiled HLO with its metadata taken out (the op metadata and
    the stack-frame tables) and its instruction names numbered in order
    of appearance, which differ between two traces of one function."""
    lines = text.splitlines()
    body = "\n".join(lines[:1] + [ln for ln in lines[1:] if ln.startswith(
        ("%", "ENTRY", " ", "}"))])
    body = re.sub(r",? ?metadata=\{[^}]*\}", "", body)
    ids: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(0), f"%v{len(ids)}"),
                  body)


@pytest.mark.parametrize("exe", ["train_step", "serve_prefill",
                                 "serve_decode"])
def test_executables_are_named(hlo, exe):
    assert hlo[exe].startswith(f"HloModule jit_{exe},")


@pytest.mark.parametrize("exe,scope", [
    ("train_step", r"/vmap\(jvp\(forward\)\)/"),
    ("train_step", r"/vmap\(transpose\(jvp\(forward\)\)\)/"),
    ("train_step", r"/optimizer/"),
    ("train_step", r"\(forward\)\)/.*/attention/"),
    ("train_step", r"\(forward\)\)/lm_head/"),
    ("train_step", r"\(forward\)\)/layers/while/body/"),
    ("serve_prefill", r"^jit\(serve_prefill\)/.*/attention/"),
    ("serve_prefill", r"^jit\(serve_prefill\)/.*/moe/"),
    ("serve_prefill", r"^jit\(serve_prefill\)/kv_write/"),
    ("serve_prefill", r"^jit\(serve_prefill\)/lm_head/"),
    ("serve_decode", r"^jit\(serve_decode\)/.*/attention/"),
    ("serve_decode", r"^jit\(serve_decode\)/.*/moe/"),
    ("serve_decode", r"/attention/kv_write/"),
    ("serve_decode", r"^jit\(serve_decode\)/lm_head/"),
    ("serve_decode", r"^jit\(serve_decode\)/layers/while/body/"),
    ("serve_prefill", r"^jit\(serve_prefill\)/layers/while/body/"),
])
def test_device_scopes_in_the_hlo_metadata(hlo, exe, scope):
    names = _op_names(hlo[exe])
    assert any(re.search(scope, n) for n in names), sorted(names)[:40]


def test_the_optimizer_is_not_under_the_forward_scope(hlo):
    names = _op_names(hlo["train_step"])
    opt = [n for n in names if "/optimizer/" in n]
    assert opt and not any("forward" in n for n in opt)


@pytest.fixture(scope="module")
def hlo_unscoped():
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        jax.clear_caches()
        return _build_all()
    finally:
        jax.named_scope = real
        jax.clear_caches()


@pytest.mark.parametrize("exe", ["train_step", "serve_prefill",
                                 "serve_decode"])
def test_scopes_change_only_metadata(hlo, hlo_unscoped, exe):
    assert "forward" not in str(_op_names(hlo_unscoped["train_step"]))
    assert _structure(hlo[exe]) == _structure(hlo_unscoped[exe])


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------

def _kernel_call(kernel):
    """A jitted call of ``kernel``'s Pallas path and its arguments."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    if kernel == "gossip_mix":
        from repro.kernels.gossip_mix import ops
        x = sds((8, 1024), f32)
        return (jax.jit(lambda x, r: ops.gossip_mix(
            x, [r], w_self=0.5, ws=(0.5,), interpret=False)), (x, x))
    if kernel == "paged_attention":
        from repro.kernels.paged_attention import ops
        pool = sds((2, 8, 16, 128), bf16)
        return (jax.jit(lambda *a: ops.paged_attention(*a, interpret=False)),
                (sds((2, 4, 128), bf16), pool, pool, sds((2, 4), jnp.int32),
                 sds((2,), jnp.int32)))
    if kernel == "flash_attention":
        from repro.kernels.flash_attention import ops
        q = sds((1, 128, 4, 128), bf16)
        kv = sds((1, 128, 2, 128), bf16)
        return (jax.jit(lambda *a: ops.flash_attention(
            *a, causal=True, interpret=False)), (q, kv, kv))
    from repro.kernels.ssd_scan import ops
    x = sds((1, 128, 2, 64), f32)
    return (jax.jit(lambda *a: ops.ssd_scan(*a, chunk=64, interpret=False)),
            (x, sds((1, 128, 2), f32), sds((2,), f32),
             sds((1, 128, 1, 16), f32), sds((1, 128, 1, 16), f32)))


def _pallas_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for p in eqn.params.values():
            if hasattr(p, "jaxpr"):
                out += _pallas_names(getattr(p.jaxpr, "jaxpr", p.jaxpr))
    return out


@pytest.mark.parametrize("kernel", ["gossip_mix", "paged_attention",
                                    "flash_attention", "ssd_scan"])
def test_kernel_names_in_the_jaxpr(kernel):
    fn, args = _kernel_call(kernel)
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [kernel]


@pytest.mark.parametrize("kernel", ["gossip_mix", "paged_attention",
                                    "flash_attention"])
def test_kernel_names_in_the_lowered_text(kernel):
    """Lowered for the TPU (no chip needed): the Mosaic custom call names
    the kernel, so the device trace does.  (``ssd_scan``'s cumsum has no
    Mosaic lowering, so only its jaxpr is read above.)"""
    fn, args = _kernel_call(kernel)
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [kernel]


_FOUR_NODE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import re
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro import configs
    from repro.core import topology
    from repro.launch import train as train_mod
    from repro.models import model as M

    n = 4
    cfg = configs.reduced_config(configs.get_config("qwen3-0.6b"))
    mesh = Mesh(np.array(jax.devices()[:n]), ("node",))
    opt, step_for = train_mod.build_trainer(
        cfg, topology.get_topology("one_peer_exp", n), "dmsgd", 0.9,
        mesh=mesh)
    params = jax.eval_shape(lambda k: jax.tree.map(
        lambda p: jnp.broadcast_to(p, (n,) + p.shape), M.init(cfg, k)),
        jax.random.key(0))
    state = jax.eval_shape(opt.init, params)
    text = step_for.plan.lowered(
        0, params, state, {"tokens": jax.ShapeDtypeStruct((n, 2, 16),
                                                          jnp.int32)},
        jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    assert text.startswith("HloModule jit_train_step,"), text[:80]
    permutes = [re.search(r'op_name="([^"]*)"', ln).group(1)
                for ln in text.splitlines()
                if re.match(r"\\s*%\\S+ = .* collective-permute", ln)]
    assert len(permutes) == 1, permutes
    assert "/optimizer/" in permutes[0], permutes
    assert "gossip/permute" in permutes[0], permutes
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for phase in ("pack", "combine"):
        assert any("/optimizer/" in x and f"gossip/{phase}/" in x
                   for x in names), (phase, sorted(names))
    print("FOUR-NODE-SCOPES-OK")
""")


def test_one_peer_step_names_its_gossip_phases(tmp_path):
    """On 4 virtual CPU devices the one-peer DmSGD step's one
    collective-permute is ``gossip/permute`` under ``optimizer``, and the
    pack and the combine carry their own scopes.  Own process: the host
    device count locks at the first JAX call."""
    script = tmp_path / "four_node.py"
    script.write_text(_FOUR_NODE_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_NUM_CPU_DEVICES", None)
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FOUR-NODE-SCOPES-OK" in r.stdout


# ---------------------------------------------------------------------------
# the engine: counters, timestamps, host spans
# ---------------------------------------------------------------------------

def _submit(eng, lengths, max_new=3):
    rng = np.random.default_rng(0)
    vocab = eng.cfg.vocab_size
    return [eng.submit(rng.integers(0, vocab, n), max_new) for n in lengths]


def test_prefill_counters():
    """Lengths 20 and 40 at page 16 make one (2, 64) prefill call: 128
    slots for 60 real tokens."""
    eng = _engine()
    _submit(eng, [20, 40])
    eng.step()
    s = eng.stats()
    assert (s["prefill_tokens"], s["prefill_slots"]) == (60, 128)
    assert s["decoded_tokens"] == 0
    eng.step()
    s = eng.stats()
    assert (s["prefill_tokens"], s["prefill_slots"]) == (60, 128)
    assert s["decoded_tokens"] == 2


def test_timestamps_are_ordered_and_stamped_on_the_host():
    """``t_submit <= t_admit <= t_first_token <= t_finish``, and a token is
    stamped only once its logits are on the host (after ``_sample`` got a
    host row)."""
    eng = _engine()
    sampled: dict = {}
    sample = eng._sample

    def recording(row, req):
        assert isinstance(row, np.ndarray)
        sampled.setdefault(req.rid, time.perf_counter())
        return sample(row, req)

    eng._sample = recording
    t0 = time.perf_counter()
    reqs = _submit(eng, [5, 9, 17])
    eng.run()
    t1 = time.perf_counter()
    for r in reqs:
        assert t0 <= r.t_submit <= r.t_admit <= r.t_first_token \
            <= r.t_finish <= t1
        assert r.t_first_token >= sampled[r.rid]


def test_latency_summary_reads_submission_stamps():
    eng = _engine()
    trace = [(0.0, np.arange(6) % eng.cfg.vocab_size, 3),
             (0.01, np.arange(9) % eng.cfg.vocab_size, 2)]
    serve_mod.serve_trace(eng, trace)
    lat = serve_mod.latency_summary(eng.finished)
    assert len(eng.finished) == 2
    assert 0 < lat["first_token_p50_s"] <= lat["total_p99_s"]


def _host_spans(trace_dir, prefix):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.end_ns)
                           for e in line.events if e.name.startswith(prefix))
    return sorted(out, key=lambda x: x[1])


def test_engine_spans_nest_in_a_profiler_session(tmp_path):
    """Two engine steps under a CPU profiler session: one carries a
    prefill, the next a decode; each phase's spans lie inside their step,
    in the order inputs, dispatch, fetch, sample."""
    eng = _engine()
    _submit(eng, [7, 12])
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path), "repro.serve.")
    steps = [s for s in spans if s[0] == "repro.serve.step"]
    assert len(steps) == 2
    for (_, lo, hi), phase in zip(steps, ["prefill", "decode"]):
        inner = [n for n, s, e in spans
                 if n != "repro.serve.step" and lo <= s and e <= hi]
        assert inner == ["repro.serve.plan", f"repro.serve.{phase}.inputs",
                         f"repro.serve.{phase}.dispatch",
                         f"repro.serve.{phase}.fetch", "repro.serve.sample"]
