"""The ``mla_moe`` architecture description (``bench/archs/mla_moe.py``):
it reads DeepSeek-V2-Lite's file and refuses what it would misread, its
counts are the published shapes', and at a small size on the CPU its
plain reference agrees with the program's set-up steps through
``bench.program.Program`` while the int8 and fp8 controls and a planted
fault fail the check."""

import copy

import jax
import pytest

from bench import arch, cells, check, faults, reference, run
from bench.cells import Cell

NAME = "dsv2lite.6l.s4096.m4"
SEED = 2**31 + 12345
# Limits for the tiny cell, set as the chip's are (bench/limits): between
# the largest reading of sound runs and the smallest of the controls, on
# the CPU at this size (the program in interpret mode, seeds 1-3 and
# 2**31 + 12345): sound at most 6.0e-4 (loss), 1.2e-2 (grad), 2.1e-3
# (update), 1.9e-2 (grad_diff); the int8 and fp8 controls at least
# 1.4e-3, 1.8e-2, 1.2e-2 and 0.11.
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1.5e-2, "update_gap": 6e-3,
          "grad_diff": 5e-2}


def _real():
    return cells.resolve(NAME)


def tiny_cell():
    """The cell at smoke widths: one dense and two MoE layers, 8 of 16
    routed experts held, top-4, one sequence of 128 tokens a microbatch
    (C = round(128 * 4 / 16 * 1.25) = 40 slots, 8-aligned for the kernel)."""
    real = _real()
    c = copy.deepcopy(real.config)
    c.update(hidden_size=128, intermediate_size=256, kv_lora_rank=64,
             num_attention_heads=2, num_key_value_heads=2,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
             moe_intermediate_size=64, n_routed_experts=8,
             num_experts_per_tok=4, num_hidden_layers=3, vocab_size=512)
    c["deployment"] = dict(c["deployment"], n_routed_experts=16)
    t = dict(real.traffic, seq_len=128, pool=4, global_batch=2, n_micro=2)
    return Cell(name=NAME, chips=1, config=c, traffic=t, limits=LIMITS,
                end_to_end=real.end_to_end, per_layer=real.per_layer)


def test_reads_the_published_file():
    config = _real().config
    desc = arch.of(config)
    d = desc.dims_of(config)
    assert (d.h, d.n_h, d.d_nope, d.d_rope, d.d_v, d.d_c) == \
        (2048, 16, 128, 64, 128, 512)
    assert (d.layers, d.dense_layers, d.ff) == (5, 1, 10944)
    assert (d.experts, d.held, d.top_k, d.expert_ff, d.shared) == \
        (64, 8, 6, 1408, 2)
    assert d.vocab == 12800 and d.aux_coef == 0.001
    spec, fixed = desc.spec_of(config)
    assert spec.mla.d_cq is None and spec.yarn.factor == 40.0
    assert (spec.moe.n_routed, spec.moe.held) == (64, 8)
    assert not spec.moe.norm_topk and spec.moe.aux_coef == 0.001
    assert spec.n_moe_layers() == 4
    assert fixed == {"router_impl": "softmax", "capacity_factor": 1.25}


@pytest.mark.parametrize("key, value", [
    ("num_experts", 64), ("router_aux_loss_coef", 0.01),
    ("qkv_bias", True), ("head_dim", 192)])
def test_refuses_a_key_it_does_not_read(key, value):
    config = dict(_real().config, **{key: value})
    with pytest.raises(ValueError, match=repr(key)):
        arch.of(config).dims_of(config)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("norm_topk_prob", True),
    ("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy"),
    ("tie_word_embeddings", True), ("num_key_value_heads", 1),
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("rope_scaling", {"type": "yarn", "factor": 40, "mscale": 1.0,
                      "mscale_all_dim": 0.707, "beta_fast": 32,
                      "beta_slow": 1,
                      "original_max_position_embeddings": 4096})])
def test_refuses_a_value_it_does_not_build(key, value):
    config = dict(_real().config, **{key: value})
    with pytest.raises(ValueError):
        arch.of(config).dims_of(config)


def test_the_decoder_refuses_this_file():
    config = dict(_real().config, arch="decoder")
    with pytest.raises(ValueError, match="does not read"):
        arch.of(config).dims_of(config)


def test_counts():
    cell = _real()
    desc = arch.of(cell.config)
    # per layer: W^Q, W^DKV, W^KR, W^UK, W^UV, W^O
    attn = 2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 512 * 16 * 128 \
        + 512 * 16 * 128 + 16 * 128 * 2048
    # router, 2 shared experts, 6 of 64 experts' share of the 8 held
    moe = 2048 * 64 + 3 * 2048 * 1408 * (2 + 6 * 8 / 64)
    active = 5 * attn + 3 * 2048 * 10944 + 4 * moe + 2048 * 12800
    want = 6 * active + 6 * 5 * 2048 * 16 * (192 + 128)
    assert desc.flops_per_token(cell.config, 4096) == pytest.approx(want)
    assert want == pytest.approx(1.862e9, rel=1e-3)
    f, b = desc.call_work("_flash_attention_jit", cell.config, cell.traffic)
    assert f == 2 * 16 * 4096 ** 2 / 2 * (192 + 128)
    assert b == 2 * 4096 * 16 * (192 * 2 + 128 * 2)
    # the 4096 x 6 x 8 / 64 = 3072 rows routed to the held experts
    f, b = desc.call_work("_gmm_jit", cell.config, cell.traffic)
    assert f == 2 * 3072 * 2048 * 1408
    assert b == 2 * (3072 * 2048 + 8 * 2048 * 1408 + 3072 * 1408)


def test_sound_run_is_correct():
    out = run.run_cell(tiny_cell(), SEED, 0.5, False, jax.devices())
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"tokens_per_s", "hbm_peak_gib",
                                   "setup_s"}


@pytest.fixture(scope="module")
def sound_reference():
    cell = tiny_cell()
    return cell, reference.readings(cell.config, cell.traffic, SEED)


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_control_fails(precision, sound_reference):
    cell, ref = sound_reference
    control = reference.readings(cell.config, cell.traffic, SEED,
                                 precision=precision)
    ok, numbers = check.judge(check.gaps(control, ref), cell.limits)
    assert not ok, numbers
    assert numbers["grad_diff"]["value"] > cell.limits["grad_diff"]


def test_fault_is_caught():
    out = run.run_cell(tiny_cell(), SEED, 0.5, False, jax.devices(),
                       step_wrapper=faults.FAULTS["half_batch"])
    assert not out["correct"], out["check"]
