"""``chip_smoke.py`` on the CPU: each phase at ``reduced_config`` with the
Pallas kernels in interpret mode, and the script's refusal to run -- or
print a result -- without a TPU."""
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase_train_reduced():
    res = chip_smoke.phase_train(reduced=True, seq=32)
    assert res["seen_loss_after"] < res["seen_loss_before"]
    assert len(res["losses"]) == 6


def test_phase_serve_reduced():
    res = chip_smoke.phase_serve(reduced=True)
    assert res["pallas_executables"] == res["jnp_executables"] == 2
    assert res["decode_logits_rel_err"] <= chip_smoke.LOGIT_RTOL


def test_phase_gossip_reduced():
    res = chip_smoke.phase_gossip(reduced=True)
    assert res["max_abs_err"] == 0.0


_FOUR_CHIP = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    import chip_smoke
    from repro.launch import train
    res = chip_smoke.phase_four_chip(reduced=True, seq=32)
    assert res["devices"] == [0, 1, 2, 3], res
    try:
        train.run(chip_smoke._train_args(reduced=True, nodes=2, steps=1))
    except ValueError as e:
        assert "--nodes 2 != 4 visible devices" in str(e), e
    else:
        raise AssertionError("2 nodes on 4 devices must be refused")
    print("FOUR-CHIP-OK")
""")


def test_phase_four_chip_on_four_cpu_devices(tmp_path):
    """The four-chip phase on 4 virtual CPU devices, in its own process
    (the device count locks at jax's first use); the gossip combine is
    forced onto the Pallas kernel in interpret mode, as the chip runs it."""
    script = tmp_path / "four.py"
    script.write_text(_FOUR_CHIP.format(repo=REPO))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", REPRO_GOSSIP_PALLAS="interpret",
               JAX_NUM_CPU_DEVICES="4")
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FOUR-CHIP-OK" in r.stdout


@pytest.mark.parametrize("argv", [[], ["--four-chip"]])
def test_main_refuses_without_a_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main(argv) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "script-alone"])
def test_script_fails_without_a_tpu(tmp_path, alone):
    path = os.path.join(REPO, "chip_smoke.py")
    if alone:
        path = shutil.copy(path, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(path)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_persistent_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert cache.enable_persistent_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache.enable_persistent_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

