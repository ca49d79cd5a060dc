"""Tiny cells of the benchmark for the CPU: the harness's code paths at
sizes a test run can hold (the Pallas kernels in interpret mode)."""

import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


# Limits for the tiny cells, set as the chip's are (bench/limits): between
# the largest reading of sound runs and the smallest of the controls, on
# the CPU at these sizes (the program in interpret mode, seeds 1-3 and
# 2**31 + 12345; dense / moe / moe4): sound at most 3.0e-4 / 8.4e-4 /
# 3.6e-4 (loss), 3.6e-3 / 1.2e-2 / 1.3e-2 (grad), 4.9e-3 / 3.0e-3 /
# 2.0e-3 (update), 1.0e-2 / 4.4e-2 / 3.1e-2 (grad_diff); the int8 and
# fp8 controls at least 9.4e-4 / 1.3e-3 / 2.6e-3, 1.4e-2 / 2.4e-2 /
# 6.2e-2, 5.3e-3 / 5.1e-3 / 6.4e-3, and 0.10 / 0.13 / 0.11.
TINY_LIMITS = {"loss_gap": 2e-3, "grad_gap": 2e-2, "update_gap": 5e-2,
               "grad_diff": 7e-2}


def _shrink(config, **sizes):
    c = copy.deepcopy(config)
    c.update(sizes)
    return c


NAMES = {"moe": "olmoe.1l.s4096.m4", "dense": "qwen2.8l.s2048.m1",
         "moe4": "olmoe.1l.ep2.s4096.m2"}


def tiny_cell(kind: str):
    """A ``bench.cells.Cell`` of the given kind ("moe", "dense", or "moe4",
    the MoE over four devices) at smoke widths, with ``TINY_LIMITS``."""
    from bench.cells import Cell, resolve
    name = NAMES[kind]
    real = resolve(name)
    if kind in ("moe", "moe4"):
        config = _shrink(real.config, hidden_size=128, intermediate_size=128,
                         num_attention_heads=2, num_key_value_heads=2,
                         num_experts=8, num_experts_per_tok=2,
                         vocab_size=512)
        traffic = dict(real.traffic, seq_len=256, pool=4,
                       global_batch=2 if kind == "moe" else 4, n_micro=2)
    else:
        config = _shrink(real.config, hidden_size=128, intermediate_size=256,
                         num_attention_heads=4, num_key_value_heads=2,
                         num_hidden_layers=2, vocab_size=512)
        traffic = dict(real.traffic, seq_len=256, pool=4)
    return Cell(name=name, chips=real.chips, config=config, traffic=traffic,
                limits=TINY_LIMITS,
                end_to_end=real.end_to_end, per_layer=real.per_layer)


@pytest.fixture
def tiny():
    return tiny_cell
