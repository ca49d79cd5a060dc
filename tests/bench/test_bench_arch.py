"""A configuration's architecture description (``bench/arch.py``): the
decoder refuses a file it would misread, a file that names no description
is refused, and a second description at another root supplies the
layout, loss and count the harness uses."""

import json
import shutil

import jax
import pytest

from bench import arch, cells, reference, trace as T
from bench.program import Program

# DeepSeek-V2-Lite's config.json, as the public catalog holds it, with the
# keys the harness adds
DEEPSEEK_V2_LITE = {
    "name": "deepseek-v2-lite", "arch": "decoder",
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400,
    "parallel": {"mesh": [1, 1, 1], "zero": "none", "ep": 1, "sp": False,
                 "schedule": "1f1b"},
    "training": {"capacity_factor": 1.25}}


def _olmoe():
    return cells.resolve("olmoe.1l.s4096.m4").config


def test_decoder_refuses_a_deepseek_file():
    # read as a decoder, the file would be a dense GQA model of d_head
    # 2048 / 16 with no latent attention and no shared experts
    desc = arch.of(DEEPSEEK_V2_LITE)
    with pytest.raises(ValueError, match="'first_k_dense_replace'"):
        desc.dims_of(DEEPSEEK_V2_LITE)


@pytest.mark.parametrize("key, value", [
    ("kv_lora_rank", 512), ("q_lora_rank", None), ("n_routed_experts", 64),
    ("n_shared_experts", 2), ("first_k_dense_replace", 1),
    ("moe_intermediate_size", 1408), ("scoring_func", "softmax")])
def test_decoder_refuses_a_key_it_does_not_read(key, value):
    config = dict(_olmoe(), **{key: value})
    with pytest.raises(ValueError, match=repr(key)):
        arch.of(config).dims_of(config)


@pytest.mark.parametrize("key, value", [
    ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("attention_bias", True), ("num_key_value_heads", 3),
    ("num_attention_heads", 15)])
def test_decoder_refuses_a_value_it_does_not_build(key, value):
    config = dict(_olmoe(), **{key: value})
    with pytest.raises(ValueError, match=f"{key}|{value}"):
        arch.of(config).dims_of(config)


def test_a_file_without_a_description_is_refused():
    config = {k: v for k, v in _olmoe().items() if k != "arch"}
    with pytest.raises(KeyError, match="arch"):
        arch.of(config)


def test_a_description_that_names_no_file_is_refused():
    with pytest.raises(FileNotFoundError, match="no-such-arch"):
        arch.of(dict(_olmoe(), arch="no-such-arch"))


# a second description: the decoder's layout, spec and work, its loss plus
# one, and another count of FLOPs
TRIVIAL = '''
from bench import arch

_decoder = arch.load({decoder!r})
dims_of, spec_of, call_work = (_decoder.dims_of, _decoder.spec_of,
                               _decoder.call_work)


def layout(d):
    tree = _decoder.layout(d)
    {drop}
    return tree


def micro_loss(w, tokens, weight, d, precision="float32", dp=1, ep=1):
    return _decoder.micro_loss(w, tokens, weight, d, precision, dp, ep) + 1.0


def flops_per_token(config, seq_len):
    return 1e9
'''
SEED = 2**31 + 12345


@pytest.fixture
def other_root(tmp_path, monkeypatch, tiny):
    """A root that holds the descriptions ``trivial`` and ``misfit`` (a
    layout without the final norm) beside a copy of ``decoder``, and one
    cell of the tiny dense configuration under ``trivial``."""
    (tmp_path / "bench" / "archs").mkdir(parents=True)
    decoder = str(arch.path_of("decoder"))
    shutil.copy(decoder, tmp_path / "bench" / "archs")
    for name, drop in (("trivial", ""), ("misfit", 'del tree["final_norm"]')):
        (tmp_path / "bench" / "archs" / f"{name}.py").write_text(
            TRIVIAL.format(decoder=decoder, drop=drop))
    cell = tiny("dense")
    config = dict(cell.config, arch="trivial")
    files = {"bench/configs/tiny.json": config,
             "bench/traffic/tiny.json": cell.traffic,
             "bench/limits/tiny.dense.json": cell.limits,
             "BENCHMARK.json": {
                 "configs": [{"name": "tiny", "file":
                              "bench/configs/tiny.json"}],
                 "workloads": [{"name": "tiny.dense", "config": "tiny",
                                "traffic": "tiny", "chips": 1}],
                 "end_to_end": [], "per_layer": []}}
    for rel, body in files.items():
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(json.dumps(body))
    monkeypatch.setattr(arch, "ROOT", tmp_path)
    return tmp_path


def test_another_description_is_the_one_used(other_root):
    cell = cells.resolve("tiny.dense", root=other_root)
    assert cell.config["arch"] == "trivial"
    config, traffic = cell.config, cell.traffic
    # Program checks the program's parameter tree against its layout
    Program(config, traffic, jax.devices())
    with pytest.raises(ValueError, match="bench/archs/misfit.py"):
        Program(dict(config, arch="misfit"), traffic, jax.devices())
    # the reference takes its loss: the decoder's plus one, the same
    # gradients
    mine = reference.readings(config, traffic, SEED, steps=1)
    base = reference.readings(dict(config, arch="decoder"), traffic, SEED,
                              steps=1)
    assert mine["loss"][0] == pytest.approx(base["loss"][0] + 1.0, rel=1e-6)
    assert mine["grad"] == pytest.approx(base["grad"], rel=1e-5)
    # step_mfu takes its count: one step of 1e9 FLOPs a token in 1 s
    tr = T.Trace({0: [("fusion.1", 0, 10)]}, [("window", 0, 10**9)])
    ctx = {"config": config, "traffic": traffic, "chips": 1, "steps": 1,
           "kind": "TPU v5 lite"}
    tokens = int(traffic["global_batch"]) * int(traffic["seq_len"])
    got = cells.load_metric("step_mfu")(tr, ctx)
    assert got == pytest.approx(100 * tokens * 1e9 / 197e12)
