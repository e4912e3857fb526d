"""End-to-end system tests: train -> checkpoint -> restore -> serve."""
import types

import jax
import numpy as np

from repro import checkpoint, configs
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod
from repro.models import model as M


def _args(**kw):
    # one node per device where several are visible (the trainer refuses
    # any other count); four nodes stacked on a single device otherwise
    nodes = jax.device_count() if jax.device_count() > 1 else 4
    base = dict(arch="qwen3-0.6b", reduced=True, nodes=nodes,
                topology="one_peer_exp", optimizer="dmsgd", beta=0.9,
                steps=25, batch=2, seq=32, lr=0.05, warmup=5, hetero=0.3,
                micro_batch=None, seed=0, desync=False, log_every=10,
                ckpt_dir=None, ckpt_every=10)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_train_loss_decreases_and_consensus():
    out = train_mod.run(_args())
    hist = out["history"]
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.5
    # decentralized replicas stay near consensus through training
    assert hist[-1]["consensus"] < 1.0


def test_train_checkpoint_roundtrip(tmp_path):
    ck = str(tmp_path / "ck")
    out = train_mod.run(_args(steps=21, ckpt_dir=ck, ckpt_every=10))
    step = checkpoint.latest_step(ck)
    assert step == 20
    like = {"params": out["params"], "momentum": out["state"].momentum}
    restored = checkpoint.restore(ck, step, like)
    assert set(restored) == {"params", "momentum"}
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(out["params"])):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_serve_generate_roundtrip():
    cfg = configs.reduced_config(configs.get_config("qwen3-0.6b"))
    params = M.init(cfg, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (2, 6), 0,
                                 cfg.vocab_size)
    out = serve_mod.generate(cfg, params, prompts, max_new=5, cache_len=16,
                             seed=0)
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out[:, :6]),
                                  np.asarray(prompts))
    assert int(out.min()) >= 0 and int(out.max()) < cfg.vocab_size


def test_train_optimizer_variants_run():
    for opt in ("dsgd", "vanilla_dmsgd", "qg_dmsgd", "parallel_msgd"):
        out = train_mod.run(_args(steps=6, optimizer=opt, log_every=5))
        assert np.isfinite(out["history"][-1]["loss"])


def test_train_overlap_end_to_end(tmp_path):
    """--overlap through the full driver: pipelined steps train, the
    in-flight buffer rides the checkpoints (carry-buffer mode), and the
    returned iterates are flushed (buf drained)."""
    ck = str(tmp_path / "ck")
    out = train_mod.run(_args(steps=11, overlap=True, ckpt_dir=ck,
                              ckpt_every=5, log_every=5))
    assert np.isfinite(out["history"][-1]["loss"])
    assert out["state"].buf is None          # final flush drained it
    step = checkpoint.latest_step(ck)
    assert step == 10
    # the carry-buffer checkpoint persisted the live in-flight payload
    import json, os
    with open(os.path.join(ck, f"step_{step}", "manifest.json")) as f:
        manifest = json.load(f)
    assert "'gossip_buf'" in manifest["treedef"]
