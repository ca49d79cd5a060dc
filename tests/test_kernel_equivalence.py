"""Pallas-vs-reference equivalence for the kernel-backend dispatch.

Three tiers, matching the dispatch layers in ``repro.models.backend``:

* standalone ops — bf16 forward AND gradient agreement for rmsnorm /
  flash attention / grouped-mlp between ``backend="pallas"`` (interpret
  mode on CPU) and the jnp reference;
* the 3D executor — one pp2×dp2×tp2 pipeline step under
  ``ModelOptions(backend="pallas")`` reproduces the reference step's loss
  and first-moment norms (subprocess with XLA_FLAGS fake devices, same
  harness as test_zero3_equivalence);
* the memory model — ``attn_impl="flash"`` drops *exactly* the
  5·b·n_h·s² score/softmax/mask term at AC-None and nothing else
  (hypothesis property over b/s/tp/recompute).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import backend as B

# bf16 tolerances: the pallas kernels accumulate in fp32 but inputs and
# outputs are bf16 (~3 decimal digits); the attention backward kernel also
# rounds P and dS to bf16 before their matmuls, as FlashAttention-2 does.
ATOL_FWD, ATOL_GRAD = 5e-2, 5e-2


def _assert_close(tag, a, b, atol):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, atol=atol, rtol=atol, err_msg=tag)


# ---------------------------------------------------------------------------
# Standalone ops: forward + grads, bf16
# ---------------------------------------------------------------------------

def test_rmsnorm_equivalence_bf16():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 256), jnp.bfloat16)
    p = {"scale": jnp.ones((256,), jnp.bfloat16)
         + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (256,), jnp.bfloat16)}

    for gemma in (False, True):
        def f(params, inp, backend):
            y = B.rmsnorm(params, inp, 1e-6, gemma_style=gemma,
                          backend=backend)
            return jnp.sum(y.astype(jnp.float32) ** 2), y

        (l_r, y_r), g_r = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
            p, x, "reference")
        (l_p, y_p), g_p = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
            p, x, "pallas")
        _assert_close(f"rmsnorm fwd gemma={gemma}", y_p, y_r, ATOL_FWD)
        assert abs(float(l_p) - float(l_r)) < 1e-2 * max(abs(float(l_r)), 1.0)
        _assert_close("rmsnorm dscale", g_p[0]["scale"], g_r[0]["scale"],
                      ATOL_GRAD * 10)     # dscale sums 64 rows of bf16
        _assert_close("rmsnorm dx", g_p[1], g_r[1], ATOL_GRAD)


def test_flash_attention_equivalence_bf16():
    b, s, nh, d = 2, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, s, nh, d), jnp.bfloat16) for kk in ks)
    scale = d ** -0.5

    def f(q_, k_, v_, impl):
        y = B.attention(q_, k_, v_, scale=scale, impl=impl)
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    (_, y_r), g_r = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, "naive")
    (_, y_p), g_p = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, "pallas")
    _assert_close("attn fwd", y_p, y_r, ATOL_FWD)
    for name, gp, gr in zip("qkv", g_p, g_r):
        _assert_close(f"attn d{name}", gp, gr, ATOL_GRAD)


def test_mla_attention_equivalence_bf16_dq_neq_dv():
    # MLA shape: query/key dim (d_h + d_hr) != value dim d_v
    b, s, nh, dq, dv = 1, 128, 2, 96, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (b, s, nh, dq), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, nh, dq), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, nh, dv), jnp.bfloat16)
    scale = dq ** -0.5

    def f(q_, k_, v_, impl):
        y = B.mla_attention(q_, k_, v_, scale=scale, impl=impl)
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    (_, y_r), g_r = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, "naive")
    (_, y_p), g_p = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, "pallas")
    assert y_p.shape == (b, s, nh, dv)
    _assert_close("mla fwd dq!=dv", y_p, y_r, ATOL_FWD)
    for name, gp, gr in zip("qkv", g_p, g_r):
        assert gp.shape == gr.shape
        _assert_close(f"mla d{name} dq!=dv", gp, gr, ATOL_GRAD)


def test_pallas_attention_gradient_holds_no_s2_buffer():
    """The gradient of the pallas attention, lowered at s 1024 (interpret
    mode, so the kernels' own bodies are in the program too), holds no
    array whose two trailing dims are (s, s); the naive path's does."""
    import re
    b, s, nh, dq, dv = 1, 1024, 2, 192, 128
    q = jax.ShapeDtypeStruct((b, s, nh, dq), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, nh, dv), jnp.bfloat16)
    s2 = re.compile(rf"tensor<(?:\d+x)*{s}x{s}x\w+>")

    def lowered(impl):
        def loss(q_, k_, v_):
            y = B.attention(q_, k_, v_, scale=dq ** -0.5, impl=impl)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, v).as_text()

    assert s2.search(lowered("naive"))
    assert not s2.search(lowered("pallas"))


def test_grouped_mlp_equivalence_bf16():
    E, C, h, f = 4, 64, 64, 128
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    buf = jax.random.normal(keys[0], (E, C, h), jnp.bfloat16)
    wg = 0.1 * jax.random.normal(keys[1], (E, h, f), jnp.bfloat16)
    wu = 0.1 * jax.random.normal(keys[2], (E, h, f), jnp.bfloat16)
    wd = 0.1 * jax.random.normal(keys[3], (E, f, h), jnp.bfloat16)

    def g(buf_, wg_, wu_, wd_, backend):
        y = B.grouped_mlp(buf_, wg_, wu_, wd_, backend=backend)
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    (_, y_r), g_r = jax.value_and_grad(g, argnums=(0, 1, 2, 3), has_aux=True)(
        buf, wg, wu, wd, "reference")
    (_, y_p), g_p = jax.value_and_grad(g, argnums=(0, 1, 2, 3), has_aux=True)(
        buf, wg, wu, wd, "pallas")
    _assert_close("gmm fwd", y_p, y_r, ATOL_FWD)
    for name, gp, gr in zip(("dbuf", "dwg", "dwu", "dwd"), g_p, g_r):
        _assert_close(f"gmm {name}", gp, gr, ATOL_GRAD)


def test_gmm_block_refuses_tiles_mosaic_refuses():
    """128 when it divides; else the whole dim — always legal for the
    column dims, legal for the capacity (row) dim only when 8-aligned."""
    assert B._gmm_block(640, rows=True) == 128
    assert B._gmm_block(40, rows=True) == 40
    assert B._gmm_block(96, rows=False) == 96
    assert B._gmm_block(5, rows=False) == 5
    with pytest.raises(ValueError, match="capacity C=5"):
        B._gmm_block(5, rows=True)
    buf = jnp.zeros((4, 5, 64), jnp.bfloat16)
    w = jnp.zeros((4, 64, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="C % 128 == 0"):
        B.grouped_mlp(buf, w, w, jnp.zeros((4, 128, 64), jnp.bfloat16),
                      backend="pallas")


def test_unsupported_flash_request_warns_with_reason():
    """Satellite: the fallback is loud and names the reason — sliding
    window and non-causal both refuse the kernel."""
    b, s, nh, d = 1, 32, 2, 16
    q = k = v = jnp.ones((b, s, nh, d), jnp.bfloat16)
    with pytest.warns(RuntimeWarning, match="sliding_window"):
        B.attention(q, k, v, scale=0.25, impl="pallas", window=8)
    with pytest.warns(RuntimeWarning, match="causal=False"):
        B.attention(q, k, v, scale=0.25, impl="pallas", causal=False)
    # the supported case is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        B.attention(q, k, v, scale=0.25, impl="pallas")


# ---------------------------------------------------------------------------
# The 3D executor: backend="pallas" inside pp2 × dp2 × tp2
# ---------------------------------------------------------------------------

PALLAS_3D = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.optim.adamw import init_train_state
    from repro.train.loop import TrainConfig
    from repro.train.pipeline_loop import make_pipeline_train_step

    spec = dataclasses.replace(get_spec("qwen2-1.5b", smoke=True), n_layers=8)
    m_ref = build_model(spec, ModelOptions(backend="reference"))
    m_pal = build_model(spec, ModelOptions(backend="pallas"))
    params = m_ref.init(jax.random.PRNGKey(0))
    batch = make_batch(config_for(spec, 8, 32), 0)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    s1, m1 = jax.jit(make_pipeline_train_step(
        m_ref, TrainConfig(n_micro=4), mesh))(init_train_state(params), batch)
    s2, m2 = jax.jit(make_pipeline_train_step(
        m_pal, TrainConfig(n_micro=4), mesh))(init_train_state(params), batch)

    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    assert dl < 5e-3, f"loss diverged: {dl}"
    # first-moment norms: the update direction each backend produced
    norms = [(float(jnp.linalg.norm(a.astype(jnp.float32))),
              float(jnp.linalg.norm(jax.device_get(b).astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(s1.m), jax.tree.leaves(s2.m))]
    worst = max(abs(a - b) / max(a, 1e-6) for a, b in norms)
    assert worst < 2e-2, f"first-moment norms diverged: {worst}"
    print("PALLAS_3D_OK", dl, worst)
""")


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_pallas_backend_reproduces_reference_3d_step():
    """pp2 × dp2 × tp2 (interpret mode): one pipeline step with
    backend="pallas" reproduces the reference step's loss and first-moment
    norms — the tentpole acceptance."""
    r = _run(PALLAS_3D)
    assert "PALLAS_3D_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


# ---------------------------------------------------------------------------
# Memory model: flash drops exactly the s² term
# ---------------------------------------------------------------------------

def test_flash_drops_exactly_the_score_term():
    pytest.importorskip(
        "hypothesis",
        reason="property test needs hypothesis (requirements-dev.txt)")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    from repro.configs import get_spec
    from repro.core.activations import (gqa_activation_bytes,
                                        mla_activation_bytes)
    from repro.core.parallel_config import RecomputePolicy

    mla_spec = get_spec("deepseek-v2")       # n_h = 128
    gqa_spec = get_spec("qwen2-1.5b")        # n_h = 12, n_kv = 2

    @settings(max_examples=60, deadline=None)
    @given(b=st.integers(1, 8), s=st.sampled_from([128, 512, 4096]),
           tp=st.sampled_from([1, 2, 4]),
           impl=st.sampled_from(["flash", "pallas"]))
    def invariant(b, s, tp, impl):
        for spec, fn in ((mla_spec, mla_activation_bytes),
                         (gqa_spec, gqa_activation_bytes)):
            kw = dict(tp=tp, sp=1, cp=1)
            scores = 5 * b * spec.n_h * s * s // tp   # tp | n_h for both specs
            naive = fn(spec, b, s, recompute=RecomputePolicy.NONE,
                       attn_impl="naive", **kw)
            flash = fn(spec, b, s, recompute=RecomputePolicy.NONE,
                       attn_impl=impl, **kw)
            # AC-None: flash subtracts the score term and nothing else
            assert naive - flash == scores, (spec.name, naive, flash, scores)
            assert flash <= naive
            # SELECTIVE already dropped it — flash must not double-subtract
            sel_n = fn(spec, b, s, recompute=RecomputePolicy.SELECTIVE,
                       attn_impl="naive", **kw)
            sel_f = fn(spec, b, s, recompute=RecomputePolicy.SELECTIVE,
                       attn_impl=impl, **kw)
            assert sel_f == sel_n == flash
            # FULL keeps only the 2bsh boundary — impl-independent
            full_n = fn(spec, b, s, recompute=RecomputePolicy.FULL,
                        attn_impl="naive", **kw)
            full_f = fn(spec, b, s, recompute=RecomputePolicy.FULL,
                        attn_impl=impl, **kw)
            assert full_f == full_n

    invariant()


@pytest.mark.parametrize("arch,b,s,tp", [
    ("deepseek-v2", 1, 4096, 2),
    ("qwen2-1.5b", 4, 512, 4),
])
def test_flash_delta_exact_deterministic(arch, b, s, tp):
    """hypothesis-free pin of the same invariant: delta == 5·b·n_h·s²/tp."""
    from repro.configs import get_spec
    from repro.core.activations import (gqa_activation_bytes,
                                        mla_activation_bytes)
    from repro.core.notation import AttentionKind
    from repro.core.parallel_config import RecomputePolicy

    spec = get_spec(arch)
    fn = mla_activation_bytes if spec.attention == AttentionKind.MLA \
        else gqa_activation_bytes
    kw = dict(tp=tp, sp=1, cp=1)
    naive = fn(spec, b, s, recompute=RecomputePolicy.NONE,
               attn_impl="naive", **kw)
    flash = fn(spec, b, s, recompute=RecomputePolicy.NONE,
               attn_impl="flash", **kw)
    assert naive - flash == 5 * b * spec.n_h * s * s // tp


def test_estimate_memory_flash_direction():
    """End to end through estimate_memory: the flash override strictly
    reduces the activation term at AC-None and touches nothing else."""
    from repro.configs import get_spec
    from repro.core.memory_model import estimate_memory
    from repro.core.parallel_config import (ParallelConfig, RecomputePolicy,
                                            ZeROStage)

    spec = get_spec("deepseek-v2")
    cfg = ParallelConfig(dp=4, tp=2, pp=4, ep=1, etp=1, sp=True,
                         zero=ZeROStage.OS, recompute=RecomputePolicy.NONE,
                         micro_batch=1, seq_len=4096)
    naive = estimate_memory(spec, cfg)
    flash = estimate_memory(spec, cfg, attn_impl="flash")
    assert flash.activations < naive.activations
    assert flash.params == naive.params
    assert flash.grads == naive.grads
    assert flash.optimizer == naive.optimizer
