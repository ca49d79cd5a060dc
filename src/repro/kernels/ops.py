"""jit'd public wrappers for the Pallas kernels.

TPU-vs-interpret contract
-------------------------
The kernels TARGET TPU.  On a TPU they always lower through Mosaic; on
the CPU they run in Pallas interpret mode (pure-jax emulation —
numerically identical, no Mosaic lowering), which is how the tests run
them.  Any other platform is an error rather than a silent emulation.
The default is decided at the FIRST kernel call, from
``jax.default_backend()``, and cached (``default_interpret``), so
importing this module initialises no backend:

* it must not be re-read inside a jitted body — ``interpret`` is a
  static argument of ``pallas_call``, so a per-call probe would bake a
  fresh Python bool into every trace and re-evaluate the backend query
  under jit for each call-site permutation;
* callers that jit *around* these wrappers (the model stack, the 3D
  executor) therefore see one stable configuration per process, which
  is the granularity at which the backend can actually change.

Pass ``interpret=`` explicitly to override per call (e.g. forcing
interpret mode on TPU for a numerics cross-check, or ``False`` to lower
for a described TPU from a CPU process).  The public wrappers are thin
Python shims that resolve the default *before* dispatching to the jitted
inner functions, so ``interpret`` reaches jit already concrete.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .mla_attention import flash_attention_pallas
from .moe_gmm import gmm_pallas, pad_groups
from .rmsnorm import rmsnorm_pallas


@functools.cache
def default_interpret() -> bool:
    """Interpret mode for the default backend: never on a TPU, always on
    the CPU; resolved at the first kernel call and cached."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels on platform {platform!r}: lower them on a "
            "TPU, or pass interpret=True explicitly")
    return platform == "cpu"


@functools.partial(jax.jit, static_argnames=("eps", "gemma_style",
                                             "block_rows", "interpret"))
def _rmsnorm_jit(x, scale, *, eps, gemma_style, block_rows, interpret):
    return rmsnorm_pallas(x, scale, eps=eps, gemma_style=gemma_style,
                          block_rows=block_rows, interpret=interpret)


def rmsnorm(x, scale, *, eps: float = 1e-6, gemma_style: bool = False,
            block_rows: int = 256, interpret: bool = None):
    interpret = default_interpret() if interpret is None else interpret
    return _rmsnorm_jit(x, scale, eps=eps, gemma_style=gemma_style,
                        block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "block_q",
                                             "block_k", "interpret"))
def _flash_attention_jit(q, k, v, *, scale, causal, block_q, block_k,
                         interpret):
    return flash_attention_pallas(q, k, v, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = None):
    interpret = default_interpret() if interpret is None else interpret
    return _flash_attention_jit(q, k, v, scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def _gmm_jit(lhs, rhs, expert_map, *, block_m, block_n, interpret):
    return gmm_pallas(lhs, rhs, expert_map, block_m=block_m, block_n=block_n,
                      interpret=interpret)


def gmm(lhs, rhs, expert_map, *, block_m: int = 128, block_n: int = 128,
        interpret: bool = None):
    interpret = default_interpret() if interpret is None else interpret
    return _gmm_jit(lhs, rhs, expert_map, block_m=block_m, block_n=block_n,
                    interpret=interpret)


__all__ = ["rmsnorm", "flash_attention", "gmm", "pad_groups",
           "default_interpret"]
