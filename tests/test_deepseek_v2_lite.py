"""DeepSeek-V2-Lite's mechanisms in the program, at smoke size on the CPU:
MLA without a query LoRA, YaRN rope, a layer that holds a share of the
routed experts, unnormalised top-k gates, the configured aux weight, and
the executor's per-kind layer stacks (a dense prefix, then MoE layers)."""

import dataclasses
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_spec
from repro.models import build_model
from repro.models import mla as M
from repro.models.layers import rope_freqs, yarn_mscale
from repro.models.moe import _route, moe_forward, moe_init
from repro.models.pipeline import chunked_partition, stack_pipeline_params

SMOKE = get_spec("deepseek-v2-lite", smoke=True)


def test_smoke_has_the_published_kinds():
    spec, full = SMOKE, get_spec("deepseek-v2-lite")
    for s in (spec, full):
        assert s.mla.d_cq is None and s.yarn is not None
        assert s.moe.first_k_dense == 1 and s.moe.n_shared == 2
        assert not s.moe.norm_topk and s.moe.aux_coef == 0.001
    assert spec.n_moe_layers() >= 3 and spec.moe.held < spec.moe.n_routed
    attn = build_model(spec).abstract_params()["moe_layers"]["attn"]
    assert "w_q" in attn and not {"w_dq", "w_uq", "w_qr", "q_norm"} & set(attn)
    m = spec.mla
    assert attn["w_q"].shape[1:] == (spec.h, spec.n_h * (m.d_h + m.d_hr))


def test_yarn_frequencies_and_mscale():
    # DeepSeek-V2's YaRN, written out: pairs i of d/2 with inverse
    # frequency theta^(-2i/d); the correction range [low, high] from the
    # dims whose wavelength turns beta_fast / beta_slow times over the
    # original length; below low the frequency is kept, above high divided
    # by the factor, linearly between
    y, d, theta = SMOKE.yarn, 64, 10000.0
    base = theta ** (-np.arange(0, d, 2) / d)

    def dim(turns):
        return d * math.log(y.original_max_position
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim(y.beta_fast)), 0)
    high = min(math.ceil(dim(y.beta_slow)), d - 1)
    assert (low, high) == (10, 23)        # d_rope 64 over 4096 positions
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    want = base * (1 - ramp) + base / y.factor * ramp
    np.testing.assert_allclose(np.asarray(rope_freqs(d, theta, y)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rope_freqs(d, theta)), base,
                               rtol=1e-6)
    # mscale = 0.1 * 0.707 * ln 40 + 1; mscale == mscale_all_dim, so cos
    # and sin keep their amplitude and the softmax scale takes mscale²
    ms = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(ms)
    assert ms ** 2 == pytest.approx(1.5896, abs=1e-4)
    with pytest.raises(ValueError, match="mscale"):
        dataclasses.replace(y, mscale=1.0)
    full = get_spec("deepseek-v2-lite")
    assert M.softmax_scale(full) == pytest.approx(192 ** -0.5 * ms ** 2)
    no_yarn = dataclasses.replace(full, yarn=None)
    assert M.softmax_scale(no_yarn) == pytest.approx(192 ** -0.5)


def test_gates_are_not_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(1), (16, SMOKE.h))
    w = jax.random.normal(jax.random.PRNGKey(2), (SMOKE.h, 8)) \
        * SMOKE.h ** -0.5
    probs, gates, eids = _route(w, SMOKE, x, "softmax")
    np.testing.assert_allclose(
        np.asarray(gates), np.take_along_axis(np.asarray(probs),
                                              np.asarray(eids), 1),
        rtol=1e-6)
    assert float(gates.sum(-1).max()) < 0.9
    norm = dataclasses.replace(SMOKE, moe=dataclasses.replace(
        SMOKE.moe, norm_topk=True))
    _, g2, _ = _route(w, norm, x, "softmax")
    np.testing.assert_allclose(np.asarray(g2.sum(-1)), 1.0, rtol=1e-5)


def _uncut():
    spec = dataclasses.replace(SMOKE, moe=dataclasses.replace(
        SMOKE.moe, n_held=None))
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     moe_init(jax.random.PRNGKey(3), spec))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, spec.h),
                          jnp.float32)
    return spec, p, x


def _share(spec, p, j, H):
    """Share ``j``: experts [jH, (j+1)H) made the held ones by rotating
    the router's columns and taking those experts' weights."""
    pj = dict(p, router=jnp.roll(p["router"], -j * H, axis=1),
              **{k: p[k][j * H:(j + 1) * H]
                 for k in ("we_gate", "we_up", "we_down")})
    sj = dataclasses.replace(spec, moe=dataclasses.replace(spec.moe,
                                                           n_held=H))
    return sj, pj


@pytest.mark.parametrize("capacity_factor", [1.25, 8.0])
def test_shares_add_up_to_the_uncut_layer(capacity_factor):
    # the routed parts of the E / held shares, with the shared experts
    # (which every chip computes alike) counted once, are the uncut
    # layer's output: each expert's slots are taken in token order
    # whichever experts a chip holds, and the capacity is that of all E
    spec, p, x = _uncut()
    E, H = spec.moe.n_routed, SMOKE.moe.held
    kw = dict(capacity_factor=capacity_factor)
    whole = moe_forward(p, spec, x, **kw)
    no_shared = dict(p, shared=jax.tree.map(jnp.zeros_like, p["shared"]))
    shared = whole.y - moe_forward(no_shared, spec, x, **kw).y
    total, routed, kept = shared, 0, 0
    for j in range(E // H):
        sj, pj = _share(spec, p, j, H)
        out = moe_forward(pj, sj, x, **kw)
        total = total + (out.y - shared)
        routed, kept = routed + int(out.routed), kept + int(out.kept)
        # the balance loss is over all E experts on every chip
        np.testing.assert_allclose(float(out.aux_loss),
                                   float(whole.aux_loss), rtol=1e-6)
    # float32 throughout: the sums differ from the one-chip layer by
    # rounding only
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole.y),
                               atol=1e-5)
    assert routed == int(whole.routed) == x.shape[0] * x.shape[1] \
        * spec.moe.n_active
    assert kept == int(whole.kept)
    if capacity_factor > 4:
        assert kept == routed


def test_every_expert_held_is_the_program_as_before():
    # n_held == n_routed compiles the same program as no n_held at all
    spec, p, x = _uncut()
    held = dataclasses.replace(spec, moe=dataclasses.replace(
        spec.moe, n_held=spec.moe.n_routed))
    f = lambda s: jax.jit(lambda p_, x_: moe_forward(p_, s, x_).y
                          ).lower(p, x).as_text()
    assert f(spec) == f(held)


def _leaves(tree):
    return {jax.tree_util.keystr(k): v.shape
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("pp", [1, 2])
def test_stacks_hold_each_kind_once(pp):
    spec = SMOKE
    params = build_model(spec).abstract_params()
    st = jax.eval_shape(lambda p: stack_pipeline_params(p, spec, pp),
                        params)["layers"]
    assert set(st) == {"dense_layers", "moe_layers"}
    assert "mlp" in st["dense_layers"] and "moe" not in st["dense_layers"]
    assert "moe" in st["moe_layers"] and "mlp" not in st["moe_layers"]
    part = chunked_partition(spec, pp)
    widths = {k.kind: k.mask.shape[-1] for k in part.slots}
    for kind in st:
        want = {k: (pp, 1, widths[kind]) + s[1:]
                for k, s in _leaves(params[kind]).items()}
        assert _leaves(st[kind]) == want
    if pp == 1:
        # one chip holds every layer once: the stacks are the parameters
        n = lambda t: sum(math.prod(s) for s in _leaves(t).values())
        assert n(st) == n({k: params[k] for k in st})
    else:
        # layers 0-1 and 2-3: the second chunk's dense slot is a pad
        assert widths == {"dense_layers": 1, "moe_layers": 2}
        dense = part.slots[0]
        assert dense.mask[:, 0, 0].tolist() == [1.0, 0.0]


def test_all_moe_stack_is_the_one_it_was():
    # an all-MoE model has one stack, laid out as the executor laid out
    # its layers before the stacks were split by kind: every slot of the
    # widest chunk, pads repeating the chunk's last layer
    spec = dataclasses.replace(get_spec("olmoe-1b-7b", smoke=True),
                               n_layers=3)
    params = build_model(spec).abstract_params()
    st = jax.eval_shape(lambda p: stack_pipeline_params(p, spec, 2),
                        params)["layers"]
    assert list(st) == ["moe_layers"]
    assert _leaves(st["moe_layers"]) == {
        k: (2, 1, 2) + s[1:] for k, s in _leaves(params["moe_layers"]).items()}
    (k,) = chunked_partition(spec, 2).slots
    assert k.idx.tolist() == [[[0, 1]], [[2, 2]]]
    assert k.mask.tolist() == [[[1, 1]], [[1, 0]]]
    assert (k.moe_flag == k.mask).all()


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _scan_bodies(jaxpr):
    """Every scan's body jaxpr, nested ones included."""
    for e in _eqns(jaxpr):
        if e.primitive.name == "scan":
            yield e.params["jaxpr"].jaxpr


def _own_dims(body):
    """The dims of the dot operands directly in ``body`` (not inside a
    nested scan), and whether it scatters (the expert dispatch)."""
    dims, scatter = set(), False
    stack = list(body.eqns)
    while stack:
        e = stack.pop()
        if e.primitive.name == "scan":
            continue
        if e.primitive.name == "dot_general":
            for v in e.invars:
                dims.update(v.aval.shape)
        scatter |= e.primitive.name.startswith("scatter")
        for sub in jax.core.jaxprs_in_params(e.params):
            stack.extend(sub.eqns)
    return dims, scatter


def test_no_slot_computes_the_other_kind():
    # the step's layer scans: one whose matmuls are the dense MLP's
    # (width h_ff) and which dispatches nothing, one whose matmuls are the
    # experts' (expert and shared widths) and which holds no dense MLP
    from repro.core.parallel_config import ZeROStage
    from repro.optim.adamw import init_train_state
    from repro.parallel.compat import make_mesh
    from repro.train.loop import TrainConfig
    from repro.train.pipeline_loop import make_pipeline_train_step
    # widths of their own: the dense MLP's, one expert's, the shared ones'
    spec = dataclasses.replace(SMOKE, h_ff=320, moe=dataclasses.replace(
        SMOKE.moe, d_ff_expert=40))
    model = build_model(spec)
    mesh = make_mesh((1, 1, 1), ("pipe", "data", "model"),
                     devices=jax.devices()[:1])
    step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh,
                                    zero=ZeROStage.NONE)
    state = jax.eval_shape(lambda: init_train_state(
        model.init(jax.random.PRNGKey(0))))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
    f = spec.moe.d_ff_expert
    expert = {f, f * spec.moe.n_shared}
    dense_scans = moe_scans = 0
    for body in _scan_bodies(jaxpr):
        dims, scatter = _own_dims(body)
        if spec.h_ff in dims:
            dense_scans += 1
            assert not dims & expert and not scatter
        if dims & expert:
            moe_scans += 1
            assert spec.h_ff not in dims
    # forward and backward of each kind's scan
    assert dense_scans >= 2 and moe_scans >= 2


def test_loss_takes_the_configured_aux_weight():
    model = build_model(SMOKE)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                SMOKE.vocab)
    total, m = model.loss(params, {"tokens": tokens})
    assert float(m["aux"]) > 0
    np.testing.assert_allclose(float(total),
                               float(m["ce"]) + 0.001 * float(m["aux"]),
                               rtol=1e-6)


EXECUTOR = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp
    from repro.parallel.compat import make_mesh
    from repro.configs import get_spec
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.optim.adamw import init_train_state
    from repro.train.loop import TrainConfig, make_train_step
    from repro.train.pipeline_loop import make_pipeline_train_step

    spec = get_spec("deepseek-v2-lite", smoke=True)
    model = build_model(spec)
    state = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, 4, 32), 0)
    s1, m1 = jax.jit(make_train_step(model, TrainConfig(n_micro=2)))(
        state, batch)
    for pp in (1, 2):
        mesh = make_mesh((pp, 1, 1), ("pipe", "data", "model"),
                         devices=jax.devices()[:pp])
        step = make_pipeline_train_step(model, TrainConfig(n_micro=2), mesh)
        s2, m2 = jax.jit(step)(state, batch)
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        # the first moment after one step is (1 - b1) x the clipped mean
        # gradient: each leaf against the pp = 1 model's
        worst = max(float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(a)
                                                    + 1e-12))
                    for a, b in zip(jax.tree.leaves(s1.m),
                                    jax.tree.leaves(jax.device_get(s2.m))))
        print(f"PP{pp}", dl, worst, int(m2["moe_routed"]),
              int(m2["moe_kept"]))
""")


def test_executor_matches_the_model():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", EXECUTOR], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    rows = {l.split()[0]: [float(v) for v in l.split()[1:]]
            for l in r.stdout.splitlines() if l.startswith("PP")}
    assert set(rows) == {"PP1", "PP2"}, r.stderr[-3000:]
    for pp, (dl, worst, routed, kept) in rows.items():
        # the executor and the model run the same bf16 layers in another
        # order of accumulation: the loss within bf16 rounding of a sum of
        # 4 x 32 token losses, each gradient leaf within 2 % of its norm
        assert dl < 5e-3, (pp, dl)
        assert worst < 2e-2, (pp, worst)
        # 3 MoE layers x 2 microbatches x 64 tokens x top-2, half of them
        # to the 4 held experts of 8, on average
        assert 0 < kept <= routed < 3 * 2 * 64 * 2
