"""The limits' upper readings, as ``bench/control.py`` takes them, at tiny
widths on the CPU: the reference in the program's place, in the control
precision or with a planted fault, fails one of its cell's numbers,
where the program's own run passes them (``test_bench_rehearsal``)."""
import pytest

from benchtest import SERVE, TINY_LIMITS, TRAIN_1, tiny, tiny_mix
from bench import control
from bench.lib import harness

SEED = 2 ** 33 + 5


@pytest.fixture(scope="module")
def train_numbers():
    return {r["variant"]: r["numbers"] for r in control.readings(
        TRAIN_1, [SEED], require_tpu=False, dims=tiny())}


@pytest.fixture(scope="module")
def serve_numbers():
    return {r["variant"]: r["numbers"] for r in control.serve_readings(
        SERVE, [SEED], seconds=3.0, require_tpu=False,
        dims=tiny("granite-moe-3b-a800m"), mix=tiny_mix(SERVE))}


@pytest.mark.parametrize("variant", ["fp8", "half"])
def test_training_control_fails_a_number(train_numbers, variant):
    nums = train_numbers[variant]
    assert any(nums[k] > v for k, v in harness.limits(TRAIN_1).items()), nums


@pytest.mark.parametrize("variant,fails", [("program", False),
                                           ("fp8", True),
                                           ("altered", True)])
def test_serving_control_fails_served_gap(serve_numbers, variant, fails):
    gap = serve_numbers[variant]["served_gap"]
    assert (gap > TINY_LIMITS[SERVE]["served_gap"]) is fails, gap
