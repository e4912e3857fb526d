"""The harness's comparison catches a broken timed path: each fault that a
cell can have is planted underneath a whole CPU run (tiny widths, the
look for a chip skipped), and ``correct`` must come out false.

Training:

* ``frozen``      -- the step returns its state unchanged;
* ``half``        -- the loss takes half of each batch row, the mean over
                     the rest;
* ``no_exchange`` -- the gossip between nodes is left out (four nodes).

Serving:

* ``altered``     -- a served token is altered where the engine samples it;
* ``stale_pool``  -- the decode step hands back its KV pool unchanged, so
                     later tokens attend to keys that were never written.
"""
import json
import subprocess
import sys
import textwrap

import pytest

from benchtest import ROOT, SERVE, TRAIN_1, TRAIN_4, subprocess_env

PLANT = textwrap.dedent('''
    import json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src"]
    from repro.core import gossip
    from repro.launch import steps, train

    fault = {fault!r}
    if fault == "frozen":
        build = train.build_trainer

        def broken(*a, **k):
            # no donation, so the inputs it hands back stay alive
            opt, step_for = build(*a, **dict(k, donate=False))

            def frozen_step(step):
                f = step_for(step)

                def call(params, state, batch, lr):
                    return params, state, f(params, state, batch, lr)[2]
                return call
            frozen_step.plan = step_for.plan
            return opt, frozen_step
        train.build_trainer = broken
    elif fault == "half":
        loss = steps.train_loss_fn

        def half(params, cfg, tokens, *a, **k):
            return loss(params, cfg, tokens[:, : tokens.shape[1] // 2], *a,
                        **k)
        steps.train_loss_fn = half
    elif fault == "no_exchange":
        gossip.mix_realization = lambda t, r, **k: t
    elif fault == "altered":
        from repro.serve import engine
        sample = engine.ServeEngine._sample

        def altered(self, logits_row, req):
            tok = sample(self, logits_row, req)
            return (tok + 1) % logits_row.shape[-1] if len(
                req.generated) == 2 else tok
        engine.ServeEngine._sample = altered
    elif fault == "stale_pool":
        from repro.serve import engine
        decode = engine.ServeEngine._decode_exe

        def stale(self, Bb):
            exe = decode(self, Bb)

            def call(params, token, pool, page_table, positions):
                return exe(params, token, pool, page_table, positions)[0], \
                    pool
            return call
        engine.ServeEngine._decode_exe = stale
    from bench.tests.benchtest import run_cell, write_root
    print(json.dumps(run_cell({workload!r}, root=write_root({tmp!r}))))
''')


def _run(workload, fault, devices, tmp_path):
    script = tmp_path / "plant.py"
    script.write_text(PLANT.format(root=ROOT, fault=fault, workload=workload,
                                   tmp=str(tmp_path)))
    r = subprocess.run([sys.executable, str(script)],
                       env=subprocess_env(devices), cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("workload,fault,devices", [
    (TRAIN_1, "frozen", 1),
    (TRAIN_1, "half", 1),
    (TRAIN_4, "no_exchange", 4),
    (SERVE, "altered", 1),
    (SERVE, "stale_pool", 1),
])
def test_fault_makes_the_run_incorrect(workload, fault, devices, tmp_path):
    res = _run(workload, fault, devices, tmp_path)
    assert res["correct"] is False, res["checks"]
    failed = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert failed
