"""The check fails a broken timed path: a run at a small size on the CPU
(the look for a chip skipped, everything else as a run on the chip does
it), with each fault planted under the program's step."""

import jax
import pytest

from bench import faults, run

SEED = 2**31 + 12345


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_sound_run_is_correct(kind, tiny):
    out = run.run_cell(tiny(kind), SEED, 0.5, False, jax.devices())
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"tokens_per_s", "hbm_peak_gib",
                                   "setup_s"}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_fault_is_caught(kind, fault, tiny):
    cell = tiny(kind)
    if not faults.applies(fault, cell.config):
        # a one-chip cell has no exchange to leave out
        assert fault == "no_exchange"
        return
    out = run.run_cell(cell, SEED, 0.5, False, jax.devices(),
                       step_wrapper=faults.FAULTS[fault])
    assert not out["correct"], out["check"]
    assert out["failed"] > 0
