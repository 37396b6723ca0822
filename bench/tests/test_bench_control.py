"""The control comes out not correct: the plain reference computed with
every matmul operand in float8_e4m3fn, the nearest precision below the
configurations' bfloat16, put in the program's place and judged by each
cell's own limits, at a tiny size on the CPU, on three seeds."""

import pytest

from bench import check, harness
from bench.tests.tiny import tiny_registry


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("name", ["qwen3-0.6b.train.short-rows"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_is_not_correct(reg, name, seed):
    cell = harness.Cell(reg, name)
    pool = cell.pool(seed)
    ref = harness.reference_readings(cell, seed, pool)
    control = harness.reference_readings(cell, seed, pool, "fp8")
    limits = {k: v for k, v in cell.limits.items() if k != "backup_mismatch"}
    correct, checks = check.judge(check.gaps(control, ref), limits)
    assert not correct, checks
