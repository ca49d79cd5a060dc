"""Expert-parallel executor equivalence + analytic EP invariants.

`make_pipeline_train_step(..., ep=tp)` — expert-dim weight shards with
all-to-all token dispatch over 'model' (`models.moe._moe_forward_ep`) —
must reproduce the ep=1 step's loss / master params / first-moment norms
to bf16-accumulation tolerance, capacity-matched (capacity_factor=4.0
keeps both the global and the per-chunk routers dropless; near the
capacity cliff the two drop different tokens, a real behavioural
difference of sharded routing, not an executor bug).

Fast tier: one olmoe pp2×dp2×tp2×ep2 run with ZeRO-1 on plus the loud
EP guards, and a functional check of the a2a dispatch against the
dropless dense reference on a bare 'model' mesh.  Slow tier: the
schedule × pp{1,2} × tp2 × ep2 × sp{off,on} grid and the deepseek-v3
leg (MLA + shared expert + mixed dense/MoE + sigmoid router).

Also here (no subprocess): hypothesis invariants of the analytic MoE
activation model in ep — monotone non-increasing, with the ep delta
equal to *exactly* the `(E/ep, C, h)` dispatch-buffer terms — and the
planner/guard contract for EP configs.

Needs >1 fake device set before jax initialises — subprocess with
XLA_FLAGS (mirrors tests/test_sp_equivalence.py).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

try:  # property suite needs hypothesis; everything else runs regardless
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

    def _skip(*_a, **_k):
        return pytest.mark.skip(
            reason="property suite needs hypothesis (requirements-dev.txt)")

    given = settings = _skip

    class st:  # noqa: N801 — stand-in so strategy expressions still parse
        @staticmethod
        def _chain(*_a, **_k):
            return None
        integers = sampled_from = _chain

from repro.configs import get_spec
from repro.core import ParallelConfig, RecomputePolicy, executor_runnable
from repro.core.activations import moe_activation_bytes
from repro.core.notation import tp_violations

DS3 = get_spec("deepseek-v3")
OLMOE = get_spec("olmoe-1b-7b")
QWEN_MOE = get_spec("qwen2-moe-a2.7b")

HEADER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.core.parallel_config import ZeROStage
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.optim.adamw import init_train_state
    from repro.train.loop import TrainConfig, make_train_step
    from repro.train.pipeline_loop import make_pipeline_train_step

    def check(tag, m1, s1, m2, s2, tol_loss=5e-3, tol_p=2e-2, tol_g=5e-2):
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < tol_loss, f"{tag}: loss diverged {dl}"
        worst = max(float(jnp.abs(a - jax.device_get(b)).max())
                    for a, b in zip(jax.tree.leaves(s1.master),
                                    jax.tree.leaves(s2.master)))
        assert worst < tol_p, f"{tag}: master params diverged {worst}"
        # grads must reproduce, not just the post-update params: one AdamW
        # step from zero moments is per-leaf scale-invariant, so compare
        # the first moments m = (1-b1) g by norm (the check that catches a
        # missing — or double — router psum, which shows ratios 0.5-2.0)
        worst_g = 0.0
        for a, b in zip(jax.tree.leaves(s1.m), jax.tree.leaves(s2.m)):
            n1 = float(jnp.linalg.norm(a.astype(jnp.float32)))
            n2 = float(jnp.linalg.norm(
                jax.device_get(b).astype(jnp.float32)))
            worst_g = max(worst_g, abs(n2 / max(n1, 1e-12) - 1.0))
        assert worst_g < tol_g, \
            f"{tag}: grad (first-moment) norms diverged {worst_g}"
        print(f"{tag}_OK", dl, worst, worst_g)
""")

FAST = HEADER + textwrap.dedent("""
    spec = dataclasses.replace(get_spec("olmoe-1b-7b", smoke=True),
                               n_layers=4)
    model = build_model(spec, ModelOptions(capacity_factor=4.0))
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    mesh = make_mesh((2, 2, 2), ("pipe", "data", "model"))
    step = make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh,
                                    zero=ZeROStage.OS, ep=2)
    s2, m2 = jax.jit(step)(state, batch)
    check("PP2_DP2_TP2_EP2_ZOS", m1, s1, m2, s2, tol_loss=1e-1)

    # the a2a dispatch group is the whole 'model' axis: ep must equal tp
    try:
        make_pipeline_train_step(model, TrainConfig(n_micro=4), mesh, ep=4)
        raise SystemExit("ep != tp was accepted")
    except ValueError as e:
        assert "ep == tp" in str(e) or "a2a" in str(e), e
        print("EP_TIE_GUARD_OK")
    # and a dense model has no experts to parallelise
    dense = build_model(get_spec("qwen2-1.5b", smoke=True))
    try:
        make_pipeline_train_step(dense, TrainConfig(n_micro=4), mesh, ep=2)
        raise SystemExit("dense + ep was accepted")
    except ValueError as e:
        assert "MoE" in str(e), e
        print("EP_MOE_GUARD_OK")
""")

DENSE_REF = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_spec
    from repro.models.moe import moe_forward, moe_forward_dense_ref, moe_init
    from repro.parallel.compat import shard_map

    spec = get_spec("olmoe-1b-7b", smoke=True)      # 4 experts top-2
    mesh = make_mesh((2,), ("model",))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       moe_init(jax.random.PRNGKey(0), spec))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, spec.h), jnp.float32)
    cap = float(spec.moe.n_routed) * 4              # dropless

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=({"router": P(None, None),
                   "we_gate": P("model", None, None),
                   "we_up": P("model", None, None),
                   "we_down": P("model", None, None)}, P()),
        out_specs=(P(), P()))
    def ep_body(lp, xs):
        out = moe_forward(lp, spec, xs, capacity_factor=cap,
                          ep=2, ep_axis="model")
        return out.y, out.aux_loss

    with mesh:
        y_ep, aux_ep = jax.jit(ep_body)(p32, x)
    ref = moe_forward(p32, spec, x, capacity_factor=cap)
    dense = moe_forward_dense_ref(p32, spec, x)
    err_d = float(jnp.abs(y_ep - dense).max())
    err_s = float(jnp.abs(y_ep - ref.y).max())
    err_a = abs(float(aux_ep) - float(ref.aux_loss))
    assert err_d < 2e-3, f"EP vs dense-ref max err {err_d}"
    assert err_s < 2e-3, f"EP vs scatter max err {err_s}"
    assert err_a < 1e-5, f"EP aux vs scatter {err_a}"

    # gradients flow through both all_to_alls and the token-slice boundary
    with mesh:
        g = jax.jit(jax.grad(lambda x_: ep_body(p32, x_)[0].sum()))(x)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
    print("EP_DENSE_REF_OK", err_d, err_s, err_a)
""")

GRID_BODY = textwrap.dedent("""
    SCHEDULE = {schedule!r}
    N_CHUNKS = {n_chunks}
    spec = dataclasses.replace(get_spec("olmoe-1b-7b", smoke=True),
                               n_layers=8)
    model = build_model(spec, ModelOptions(capacity_factor=4.0))
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 8, 32), 0)
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=4)))(state, batch)
    meshes = [(1, 2, 2), (2, 2, 2)] if SCHEDULE == "1f1b" else [(2, 2, 2)]
    for pp, data, tp in meshes:
        mesh = make_mesh((pp, data, tp), ("pipe", "data", "model"))
        for sp in (False, True):
            step = make_pipeline_train_step(
                model, TrainConfig(n_micro=4), mesh, schedule=SCHEDULE,
                n_chunks=N_CHUNKS, zero=ZeROStage.OS, sp=sp, ep=tp)
            s2, m2 = jax.jit(step)(state, batch)
            check(f"PP{{pp}}_TP{{tp}}_EP{{tp}}_SP{{int(sp)}}", m1, s1, m2, s2,
                  tol_loss=1e-1)
""")

MOE_MLA_EP = HEADER + textwrap.dedent("""
    # deepseek-v3: MLA latent towers + mixed dense/MoE layers + sigmoid
    # router + a shared expert (which must stay on the ETP f/g path while
    # the routed experts dispatch over the a2a)
    spec = dataclasses.replace(get_spec("deepseek-v3", smoke=True),
                               n_layers=4)
    model = build_model(spec, ModelOptions(capacity_factor=4.0))
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 4, 32), 0)
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(state, batch)
    for sp in (False, True):
        mesh = make_mesh((2, 1, 2), ("pipe", "data", "model"))
        step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh,
                                        zero=ZeROStage.OS, sp=sp, ep=2)
        s2, m2 = jax.jit(step)(state, batch)
        check(f"DSV3_EP2_SP{int(sp)}", m1, s1, m2, s2)
""")


def grid_script(schedule, n_chunks):
    return HEADER + GRID_BODY.format(schedule=schedule, n_chunks=n_chunks)


def _run(script):
    env = dict(os.environ, PYTHONPATH="src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_ep_fast():
    """pp2 × dp2 × tp2 × ep2 with ZeRO-1 + the loud EP guards: the tier-1
    EP smoke."""
    r = _run(FAST)
    for tag in ("PP2_DP2_TP2_EP2_ZOS_OK", "EP_TIE_GUARD_OK",
                "EP_MOE_GUARD_OK"):
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_ep_dispatch_matches_dense_ref():
    """The a2a dispatch (shard-mapped over a bare 'model' mesh) equals the
    dropless dense reference AND the scatter path — output, aux and
    gradient flow — at matched capacity."""
    r = _run(DENSE_REF)
    assert "EP_DENSE_REF_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
@pytest.mark.parametrize("schedule,n_chunks",
                         [("1f1b", 1), ("interleaved", 2), ("dualpipe", 2)])
def test_ep_grid(schedule, n_chunks):
    """schedule × pp{1,2} × tp2 × ep2 × sp{off,on} vs the single-device
    (ep=1) step."""
    r = _run(grid_script(schedule, n_chunks))
    tags = ["PP2_TP2_EP2_SP0_OK", "PP2_TP2_EP2_SP1_OK"]
    if schedule == "1f1b":
        tags += ["PP1_TP2_EP2_SP0_OK", "PP1_TP2_EP2_SP1_OK"]
    for tag in tags:
        assert tag in r.stdout, \
            f"missing {tag}\nstdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_ep_moe_mla():
    r = _run(MOE_MLA_EP)
    assert "DSV3_EP2_SP0_OK" in r.stdout and "DSV3_EP2_SP1_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


# ---------------------------------------------------------------------------
# Analytic invariants (no subprocess): the (E/ep, C, h) dispatch terms
# ---------------------------------------------------------------------------

def _dispatch_terms(spec, b, s, ep, rc):
    """The routed-expert buffer bytes the model books at EP degree ``ep`` —
    the same int() placement as ``moe_activation_bytes``."""
    e = spec.moe
    n_local = e.n_routed // ep
    e_token = b * s * e.n_active / e.n_routed
    if rc == RecomputePolicy.SELECTIVE:
        return int(n_local * 2 * e_token * spec.h)
    return int(n_local * (3 * e_token * spec.h + 8 * e_token * e.d_ff_expert))


@settings(max_examples=60, deadline=None)
@given(b=st.integers(1, 4), s16=st.integers(1, 256),
       lo=st.sampled_from([1, 2, 4]), hi=st.sampled_from([4, 8, 16]),
       rc=st.sampled_from(list(RecomputePolicy)))
def test_moe_bytes_monotone_in_ep(b, s16, lo, hi, rc):
    """Larger ep never costs more, for every MoE family and recompute
    policy (every drawn degree divides both n_routed counts: 256 and 64)."""
    s = 16 * s16
    for spec in (DS3, OLMOE):
        assert moe_activation_bytes(spec, b, s, sp=1, cp=1, ep=hi,
                                    recompute=rc) \
            <= moe_activation_bytes(spec, b, s, sp=1, cp=1, ep=lo,
                                    recompute=rc)


@settings(max_examples=60, deadline=None)
@given(b=st.integers(1, 4), s16=st.integers(1, 256),
       ep=st.sampled_from([2, 4, 8, 16]),
       rc=st.sampled_from(list(RecomputePolicy)))
def test_ep_delta_is_exactly_the_dispatch_terms(b, s16, ep, rc):
    """ep=1 minus ep=k equals the shrink of *exactly* the dispatch-buffer
    terms — n_local·(3 E_token h + 8 E_token h_E) for AC-None, the kept
    n_local·2 E_token h for AC-Selective, nothing for AC-Full (only block
    inputs + router outputs are stored).  Router activations (4bsN +
    2bsN_r), residual terms and the shared expert contribute zero."""
    s = 16 * s16
    for spec in (DS3, OLMOE):
        d = moe_activation_bytes(spec, b, s, sp=1, cp=1, ep=1, recompute=rc) \
            - moe_activation_bytes(spec, b, s, sp=1, cp=1, ep=ep,
                                   recompute=rc)
        if rc == RecomputePolicy.FULL:
            assert d == 0
        else:
            assert d == _dispatch_terms(spec, b, s, 1, rc) \
                - _dispatch_terms(spec, b, s, ep, rc)


def test_indivisible_ep_warns_and_falls_back():
    """ep ∤ n_routed warns and models the buffer as EP-replicated (the
    loud-fallback contract shared with the TP/SP guards)."""
    with pytest.warns(RuntimeWarning, match="n_routed"):
        got = moe_activation_bytes(OLMOE, 2, 64, sp=1, cp=1, ep=3,
                                   recompute=RecomputePolicy.NONE)
    assert got == moe_activation_bytes(OLMOE, 2, 64, sp=1, cp=1, ep=1,
                                       recompute=RecomputePolicy.NONE)


def test_ep_violations_listed_and_executor_guards():
    """tp_violations grows the ep axis; check_ep_supported raises on the
    untieable degrees; executor_runnable marks MoE+EP configs runnable
    exactly when the executor can place them (ep == tp, divisible)."""
    assert tp_violations(OLMOE, 2, ep=2) == []
    assert any("n_routed=60" in v for v in tp_violations(QWEN_MOE, 2, ep=8))

    tp_mod = pytest.importorskip("repro.parallel.tp")
    tp_mod.check_ep_supported(OLMOE, 2, 2)                 # ok
    tp_mod.check_ep_supported(OLMOE, 2, 1)                 # ETP path, ok
    with pytest.raises(ValueError, match="tied to it"):
        tp_mod.check_ep_supported(OLMOE, 4, 2)
    with pytest.raises(ValueError, match="MoE"):
        tp_mod.check_ep_supported(get_spec("qwen2-1.5b"), 2, 2)
    with pytest.raises(ValueError, match="n_routed"):
        tp_mod.check_ep_supported(QWEN_MOE, 8, 8)
    with pytest.raises(ValueError, match="token count"):
        tp_mod.check_ep_supported(OLMOE, 2, 2, tokens_per_rank=33)

    # planner: the old flat "EP is dry-run-only" rejection is gone —
    # executor-placeable EP configs rank as runnable, the wider enumeration
    # space stays estimator-only with the reason recorded
    ok, why = executor_runnable(
        OLMOE, ParallelConfig(dp=4, tp=2, ep=2, sp=True))
    assert ok, why
    ok, why = executor_runnable(
        OLMOE, ParallelConfig(dp=4, tp=4, ep=2, sp=True))
    assert not ok and "estimator-only" in why
    ok, why = executor_runnable(
        QWEN_MOE, ParallelConfig(dp=8, tp=2, ep=8, sp=True))
    assert not ok and "n_routed" in why


def test_planner_surfaces_runnable_ep():
    """plan() over a small world produces at least one runnable EP>1 entry
    for an MoE model (the acceptance criterion's 'no longer rejecting'),
    and the estimator-only grouped-EP configs carry a precise reason.
    Runnable configs rank first (by predicted step time), so the
    estimator-only entries live past the runnable block — probe with an
    uncapped top_k."""
    from repro.core.planner import plan
    entries = plan(OLMOE, 16, 96 * 2 ** 30, seq_len=4096, top_k=50)
    assert any(e.cfg.ep > 1 and e.runnable for e in entries), \
        [(e.cfg.describe(), e.why_not_runnable) for e in entries[:10]]
    full = plan(OLMOE, 16, 96 * 2 ** 30, seq_len=4096, top_k=10 ** 6)
    kinds = {e.why_not_runnable for e in full
             if e.cfg.ep > 1 and not e.runnable}
    assert any("estimator-only" in w for w in kinds)
