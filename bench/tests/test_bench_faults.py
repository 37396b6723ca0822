"""A run with the timed path broken underneath comes out not correct, once
for each fault a cell can have; and the same run unbroken is correct.
The runs skip the look for a chip and run the rest on the CPU, at a tiny
size of every configuration."""

import time

import jax
import pytest

from bench import harness
from bench.tests.faults import run_with_fault
from bench.tests.tiny import CPU_PEAK, tiny_registry

ONE_CHIP = "qwen3-0.6b.train.short-rows"


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(reg):
    out = harness.run(reg, ONE_CHIP, 7, 0.5, False, time.perf_counter(),
                      jax.devices()[:1], peak=CPU_PEAK)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_tokens_per_s", "step_ms.p90",
                                   "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(reg, fault):
    out = run_with_fault(reg, ONE_CHIP, fault)
    assert not out["correct"], (fault, out["checks"])

