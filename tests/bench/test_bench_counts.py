"""The FLOP and byte counts of the metrics, against hand counts at one
small shape."""

from bench import cells

CONFIG = {
    "arch": "decoder",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "vocab_size": 1000, "num_hidden_layers": 2,
    "tie_word_embeddings": True, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
    "parallel": {"mesh": [1, 1, 1], "ep": 1}, "training": {}}
MOE = dict(CONFIG, num_experts=8, num_experts_per_tok=2,
           intermediate_size=32, tie_word_embeddings=False)
TRAFFIC = {"seq_len": 128, "global_batch": 4, "n_micro": 2}


def test_step_flops_dense():
    f = cells.load_metric("step_mfu").__globals__["flops_per_token"]
    # per layer: q 64*64, k and v 2 * 64*32, o 64*64, MLP 3 * 64*96
    layer = 4096 + 4096 + 4096 + 18432
    active = 2 * layer + 64 * 1000                # + the (tied) head
    attn = 6 * 2 * 64 * 4 * (16 + 16)             # 6 L (s/2) n_h (dq + dv)
    assert f(CONFIG, 128) == 6 * active + attn


def test_step_flops_moe():
    f = cells.load_metric("step_mfu").__globals__["flops_per_token"]
    # attention as above; 2 of 8 experts of 3 * 64*32, the router 64*8
    layer = 4096 + 4096 + 4096 + 2 * 3 * 64 * 32 + 64 * 8
    active = 2 * layer + 64 * 1000
    attn = 6 * 2 * 64 * 4 * 32
    assert f(MOE, 128) == 6 * active + attn


def test_flash_call_work():
    work = cells.load_metric("flash_fwd_roofline").__globals__["call_work"]
    flops, nbytes = work(CONFIG, TRAFFIC)
    b, s, n, d = 2, 128, 4, 16                    # b = 4 / 2 microbatches
    assert flops == 2 * b * n * (s * s / 2) * (d + d)
    assert nbytes == 2 * b * s * n * 4 * d        # q, k, v read; o written


def test_gmm_call_work():
    work = cells.load_metric("gmm_fwd_roofline").__globals__["call_work"]
    flops, nbytes = work(MOE, TRAFFIC)
    rows = 2 * 128 * 2                            # T k routed rows
    assert flops == 2 * rows * 64 * 32
    assert nbytes == 2 * (rows * 64 + 8 * 64 * 32 + rows * 32)


def test_gmm_call_work_under_expert_parallelism():
    work = cells.load_metric("gmm_fwd_roofline").__globals__["call_work"]
    ep = dict(MOE, parallel={"mesh": [1, 2, 2], "ep": 2})
    flops, nbytes = work(ep, TRAFFIC)
    # one sequence per data shard, half its routed rows to this chip's 4
    # experts, each at its full width 32
    rows = 1 * 128 * 2 // 2
    assert flops == 2 * rows * 64 * 32
    assert nbytes == 2 * (rows * 64 + 4 * 64 * 32 + rows * 32)


def test_roofline_share_bound():
    """A kernel whose calls take exactly their least time reads 100 %."""
    from bench.trace import roofline_share
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    ev = {0: [("k", 0, 2_000_000), ("k", 5_000_000, 7_000_000)]}
    # 2e9 FLOPs -> 2 ms at the peak; 1e8 bytes -> 1 ms: compute-bound
    assert abs(roofline_share(ev, 2e9, 1e8, peak) - 100.0) < 1e-9
    assert abs(roofline_share(ev, 1e9, 1e8, peak) - 50.0) < 1e-9
