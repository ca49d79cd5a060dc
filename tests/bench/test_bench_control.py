"""The controls fail the check: the plain reference computed in int8 and
in fp8 (both below the configured bfloat16) in the program's place,
against the float32 reference, at a small size on the CPU."""

import pytest

from bench import check, reference

SEED = 2**31 + 12345


@pytest.mark.parametrize("precision", ["int8", "fp8"])
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_control_fails(kind, precision, tiny):
    cell = tiny(kind)
    ref = reference.readings(cell.config, cell.traffic, SEED)
    control = reference.readings(cell.config, cell.traffic, SEED,
                                 precision=precision)
    ok, numbers = check.judge(check.gaps(control, ref), cell.limits)
    assert not ok, numbers
    # a rounding this coarse moves the median leaf
    assert numbers["grad_diff"]["value"] > cell.limits["grad_diff"]
