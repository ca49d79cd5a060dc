"""The Pallas kernels lower through Mosaic for a described TPU v5e.

Interpret mode (how every other test runs the kernels) accepts tilings the
chip's compiler refuses, so these tests compile each kernel of the main
path with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology, at the widths and capacities ``chip_smoke.py`` runs, and check
that the compiled program holds the kernel (``tpu_custom_call``).  Nothing
runs: a compile that passes here is not a chip run.

The topology is described only inside a fixture, so collecting this file
loads no TPU library; where it cannot be described the tests skip.  The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as K
from repro.kernels.mla_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.models import backend as B


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_rmsnorm_compiles_for_v5e(one_chip):
    """qwen2-1.5b's residual stream: (b·s, h) = (2048, 1536)."""
    x = jax.ShapeDtypeStruct((2048, 1536), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1536,), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda x_, w_: rmsnorm_pallas(x_, w_, interpret=False), x, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s, n_h, dq, dv", [
    (2048, 12, 128, 128),     # qwen2-1.5b GQA (kv heads replicated to 12)
    (4096, 12, 128, 128),
    (4096, 16, 192, 128),     # MLA: d_h + d_hr = 192 for q/k, d_v = 128
], ids=["gqa-s2048", "gqa-s4096", "mla-s4096"])
def test_flash_attention_compiles_for_v5e(one_chip, s, n_h, dq, dv):
    q = jax.ShapeDtypeStruct((1, s, n_h, dq), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, s, n_h, dv), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q_, k_, v_: flash_attention_pallas(
            q_, k_, v_, scale=dq ** -0.5, interpret=False), q, q, v)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("E, C, h, f", [
    (64, 640, 2048, 1024),    # olmoe-1b-7b, 4096 tokens top-8 at cf 1.25
    (64, 1280, 2048, 1024),   # the same at 8192 tokens
], ids=["olmoe-c640", "olmoe-c1280"])
def test_grouped_mlp_compiles_for_v5e(one_chip, monkeypatch, E, C, h, f):
    """The backend's grouped SwiGLU forward — ``_gmm_block``'s tiling and
    three GEMM kernels — lowered natively (the CPU process would pick
    interpret mode, so the test steers that choice)."""
    monkeypatch.setattr(K, "default_interpret", lambda: False)
    buf = jax.ShapeDtypeStruct((E, C, h), jnp.bfloat16, sharding=one_chip)
    wi = jax.ShapeDtypeStruct((E, h, f), jnp.bfloat16, sharding=one_chip)
    wo = jax.ShapeDtypeStruct((E, f, h), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda b_, g_, u_, d_: B.grouped_mlp(b_, g_, u_, d_,
                                             backend="pallas"),
        buf, wi, wi, wo)
    assert text.count("tpu_custom_call") >= 3
