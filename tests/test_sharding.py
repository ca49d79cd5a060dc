"""Sharding-rule unit tests: param specs follow the paper's §3 partitioning,
ZeRO stages add data-axis sharding, and a small-mesh pjit train step runs
end-to-end with sharded state."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_spec
from repro.core.parallel_config import ZeROStage
from repro.models import build_model
from repro.optim.adamw import init_train_state
from repro.parallel.compat import make_mesh
from repro.parallel.sharding import (add_dp_axes, grad_shardings,
                                     param_specs, state_shardings)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _mesh_1x1():
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def test_param_specs_follow_paper_rules():
    spec = get_spec("deepseek-v3", smoke=True)
    model = build_model(spec)
    abstract = model.abstract_params()
    mesh = _mesh_1x1()
    specs = param_specs(abstract, mesh)
    moe = specs["moe_layers"]
    # experts sharded on the expert dim (EP), ETP=1 → no inner split (§3.3)
    assert moe["moe"]["we_gate"] == P(None, "model", None, None)
    assert moe["moe"]["we_down"] == P(None, "model", None, None)
    # router replicated (§3.3)
    assert moe["moe"]["router"] == P(None, None, None)
    # MLA: up/out projections TP-split; down-projections replicated (§3.2)
    assert moe["attn"]["w_uq"] == P(None, None, "model")
    assert moe["attn"]["w_o"] == P(None, "model", None)
    assert moe["attn"]["w_dq"] == P(None, None, None)
    assert moe["attn"]["w_dkv"] == P(None, None, None)
    assert moe["attn"]["w_kr"] == P(None, None, None)
    # norms replicated
    assert moe["ln1"]["scale"] == P(None, None)
    # embedding vocab-sharded
    assert specs["embed"]["w"] == P("model", None)


def test_add_dp_axes_picks_divisible_dim():
    mesh = _mesh_1x1()
    s = add_dp_axes(P(None, "model"), (7, 64), mesh)
    assert s == P(("data",), "model") or s == P("data", "model")
    # indivisible everywhere -> unchanged
    s2 = add_dp_axes(P(), (3,), make_mesh((1,), ("data",),
                                          devices=jax.devices()[:1]))
    # 3 % 1 == 0 with a 1-sized axis; use a logical check instead:
    assert s2 in (P(("data",)), P("data"), P())


def test_zero_stage_monotone_sharding():
    """More aggressive ZeRO stages shard strictly more state pytrees."""
    spec = get_spec("qwen2-1.5b", smoke=True)
    model = build_model(spec)
    abstract_state = jax.eval_shape(init_train_state, model.abstract_params())
    mesh = _mesh_1x1()

    def count_dp(tree):
        n = 0
        for sh in jax.tree.leaves(tree,
                                  is_leaf=lambda x: isinstance(x, NamedSharding)):
            spec_ = sh.spec
            names = [a for e in spec_ if e for a in
                     ((e,) if isinstance(e, str) else e)]
            if "data" in names:
                n += 1
        return n

    none = state_shardings(abstract_state, mesh, ZeROStage.NONE)
    os_ = state_shardings(abstract_state, mesh, ZeROStage.OS)
    osgp = state_shardings(abstract_state, mesh, ZeROStage.OS_G_PARAMS)
    assert count_dp(none.master) == 0
    assert count_dp(os_.master) > 0
    assert count_dp(none.params) == 0
    assert count_dp(os_.params) == 0
    assert count_dp(osgp.params) > 0
    g_none = grad_shardings(model.abstract_params(), mesh, ZeROStage.OS)
    g_shard = grad_shardings(model.abstract_params(), mesh, ZeROStage.OS_G)
    assert count_dp(g_none) == 0
    assert count_dp(g_shard) > 0


def test_pjit_train_step_with_sharded_state():
    """End-to-end: jit with in/out shardings on a 1x1 mesh (degenerate but
    exercises the full sharding plumbing the dry-run uses)."""
    from repro.data.synthetic import config_for, make_batch
    from repro.launch.specs import batch_shardings
    from repro.parallel.axes import axis_rules
    from repro.train.loop import TrainConfig, make_train_step

    spec = get_spec("olmoe-1b-7b", smoke=True)
    model = build_model(spec)
    mesh = _mesh_1x1()
    params = model.init(jax.random.PRNGKey(0))
    state = init_train_state(params)
    abstract_state = jax.eval_shape(lambda: state)
    st_sh = state_shardings(abstract_state, mesh, ZeROStage.OS_G)
    batch = make_batch(config_for(spec, 2, 16), 0)
    b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh)
    step = make_train_step(model, TrainConfig())
    with axis_rules(mesh):
        fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None))
        new_state, metrics = fn(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert int(new_state.step) == 1
