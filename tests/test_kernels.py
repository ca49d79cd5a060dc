"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.moe_gmm import pad_groups

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (3, 7, 256), (1, 384),
                                   (300, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gemma", [False, True])
def test_rmsnorm_matches_ref(shape, dtype, gemma):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = _rand(k1, shape, dtype)
    scale = _rand(k2, shape[-1:], dtype) * 0.1
    got = ops.rmsnorm(x, scale, gemma_style=gemma, interpret=True)
    want = ref.rmsnorm_ref(x, scale, gemma_style=gemma)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("block_rows", [8, 64, 256])
def test_rmsnorm_block_sweep(block_rows):
    x = _rand(jax.random.PRNGKey(1), (100, 256), jnp.float32)
    s = jnp.ones((256,), jnp.float32)
    got = ops.rmsnorm(x, s, block_rows=block_rows, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.rmsnorm_ref(x, s)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# flash attention (MLA-shaped: dq != dv supported)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,nh,dq,dv", [
    (1, 128, 2, 64, 64),
    (2, 256, 4, 128, 128),
    (1, 200, 2, 192, 128),      # MLA geometry (d_h+d_hr=192, d_v=128), ragged s
    (2, 64, 1, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(b, s, nh, dq, dv, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(k1, (b, s, nh, dq), dtype)
    k = _rand(k2, (b, s, nh, dq), dtype)
    v = _rand(k3, (b, s, nh, dv), dtype)
    scale = dq ** -0.5
    got, _ = ops.flash_attention(q, k, v, scale=scale, block_q=64,
                                 block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=scale)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_non_causal():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(k1, (1, 128, 2, 64), jnp.float32)
    k = _rand(k2, (1, 128, 2, 64), jnp.float32)
    v = _rand(k3, (1, 128, 2, 64), jnp.float32)
    got, _ = ops.flash_attention(q, k, v, scale=0.125, causal=False,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=0.125, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [32, 128])
def test_flash_block_sweep(block):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(k1, (1, 257, 2, 64), jnp.float32)   # deliberately ragged
    k = _rand(k2, (1, 257, 2, 64), jnp.float32)
    v = _rand(k3, (1, 257, 2, 64), jnp.float32)
    got, _ = ops.flash_attention(q, k, v, scale=0.125, block_q=block,
                                 block_k=block, interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_matches_model_chunked_attention():
    """Kernel, jnp-chunked (model path), and naive oracle must all agree."""
    from repro.models.attention import chunked_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(k1, (2, 96, 2, 64), jnp.float32)
    k = _rand(k2, (2, 96, 2, 64), jnp.float32)
    v = _rand(k3, (2, 96, 2, 64), jnp.float32)
    a, _ = ops.flash_attention(q, k, v, scale=0.125, interpret=True)
    b = chunked_attention(q, k, v, 0.125, block=32)
    c = ref.flash_attention_ref(q, k, v, scale=0.125)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=2e-5, rtol=2e-5)


def test_flash_lse_is_the_rows_logsumexp():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(10), 3)
    b, s, nh, dq, dv = 2, 200, 2, 192, 128
    q = _rand(k1, (b, s, nh, dq), jnp.float32)
    k = _rand(k2, (b, s, nh, dq), jnp.float32)
    v = _rand(k3, (b, s, nh, dv), jnp.float32)
    _, lse = ops.flash_attention(q, k, v, scale=dq ** -0.5, interpret=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dq ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    assert lse.shape == (b, nh, s) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jax.nn.logsumexp(scores, -1)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,s,nh,dq,dv", [
    (2, 256, 2, 128, 128),      # GQA heads after replication
    (1, 256, 2, 192, 128),      # MLA: d_h + d_hr = 192 for q/k, d_v = 128
    (1, 200, 2, 192, 128),      # padded to 256
    (2, 100, 1, 64, 32),        # under one 128 block: padded to 104
    (1, 128, 2, 64, 64),        # block sweep (q x k): 128 x 128 once,
    (1, 384, 1, 64, 64),        # 128 x 128, three k blocks,
    (1, 1024, 1, 128, 64),      # 256 x 512, two k blocks
], ids=["gqa", "mla", "mla-pad", "short-pad", "q128k128x1", "q128k128x3",
        "q256k512x2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_bwd_matches_ref_vjp(b, s, nh, dq, dv, dtype):
    """dq, dk, dv of the backward kernel, from the forward's (o, lse),
    against ``jax.vjp`` of the naive oracle."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(11), 4)
    q = _rand(k1, (b, s, nh, dq), dtype)
    k = _rand(k2, (b, s, nh, dq), dtype)
    v = _rand(k3, (b, s, nh, dv), dtype)
    do = _rand(k4, (b, s, nh, dv), dtype)
    scale = dq ** -0.5
    o, lse = ops.flash_attention(q, k, v, scale=scale, interpret=True)
    got = ops.flash_attention_bwd(q, k, v, o, lse, do, scale=scale,
                                  interpret=True)
    _, vjp = jax.vjp(lambda *a: ref.flash_attention_ref(*a, scale=scale),
                     q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, g, w in zip(("dq", "dk", "dv"), got, vjp(do)):
        assert g.shape == w.shape and g.dtype == dtype, name
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w,
                                   atol=tol * np.abs(w).max(), rtol=tol,
                                   err_msg=name)


def test_flash_bwd_plan():
    """Blocks from (s, dq, dv) and VMEM alone: the largest k block that
    divides the padded sequence, 256-row q blocks where 256 divides it,
    and a refusal where a head cannot stay in VMEM."""
    from repro.kernels.mla_attention import _BWD_VMEM_BUDGET, _bwd_plan
    assert _bwd_plan(4096, 192, 128, 2)[:3] == (4096, 256, 512)
    assert _bwd_plan(2048, 128, 128, 2)[:3] == (2048, 256, 512)
    assert _bwd_plan(200, 192, 128, 2)[:3] == (256, 256, 256)
    assert _bwd_plan(384, 64, 64, 2)[:3] == (384, 128, 128)
    assert _bwd_plan(640, 128, 128, 4)[:3] == (640, 128, 128)
    assert _bwd_plan(100, 64, 32, 2)[:3] == (104, 104, 104)
    assert _bwd_plan(4096, 192, 128, 2)[3] <= _BWD_VMEM_BUDGET
    with pytest.raises(ValueError, match="does not fit"):
        _bwd_plan(2 ** 17, 192, 128, 2)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,K,N,block_m", [
    (4, 64, 128, 16),
    (8, 128, 256, 32),
    (3, 96, 64, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_matches_ref(E, K, N, block_m, dtype):
    rng = np.random.default_rng(0)
    group_sizes = rng.integers(0, 3 * block_m, size=E)
    rows = np.repeat(np.arange(E), group_sizes)
    T = len(rows)
    x = _rand(jax.random.PRNGKey(6), (max(T, 1), K), dtype)
    rhs = _rand(jax.random.PRNGKey(7), (E, K, N), dtype)
    lhs, emap, ridx = pad_groups(x[:T], group_sizes, block_m)
    got = ops.gmm(lhs, rhs, jnp.asarray(emap), block_m=block_m,
                  block_n=min(128, N), interpret=True)
    want = ref.gmm_ref(lhs, rhs, emap, block_m=block_m)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # padded rows scatter back losslessly
    valid = ridx >= 0
    assert valid.sum() == T


def test_gmm_against_dense_expert_loop():
    """GMM == looping each expert over its slab (semantic oracle)."""
    E, K, N, block_m = 4, 32, 64, 8
    sizes = np.array([8, 16, 0, 24])
    x = _rand(jax.random.PRNGKey(8), (int(sizes.sum()), K), jnp.float32)
    rhs = _rand(jax.random.PRNGKey(9), (E, K, N), jnp.float32)
    lhs, emap, ridx = pad_groups(x, sizes, block_m)
    got = ops.gmm(lhs, rhs, jnp.asarray(emap), block_m=block_m, block_n=64,
                  interpret=True)
    got_valid = np.asarray(got)[ridx >= 0]
    want = []
    off = 0
    for e in range(E):
        g = int(sizes[e])
        want.append(np.asarray(x[off:off + g] @ rhs[e]))
        off += g
    np.testing.assert_allclose(got_valid, np.concatenate(want), atol=1e-4,
                               rtol=1e-4)
