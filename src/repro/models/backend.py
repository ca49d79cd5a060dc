"""Kernel-backend dispatch: the ONE point where model code picks between
the jnp reference math and the Pallas fast path.

Every hot op the paper's activation tables care about — ``rmsnorm``,
``attention`` (GQA context), ``mla_attention`` (dq≠dv flash), and the MoE
``grouped_mlp`` — resolves here from ``ModelOptions.backend``
(``"reference" | "pallas"``; the legacy ``use_pallas=True`` flag is an
alias for ``"pallas"``).  Call sites: the non-pipeline path
(``transformer._norm`` / ``block_apply``), the 3D executor
(``pipeline._slot_apply`` + the chunk heads in ``train.pipeline_loop``),
the MLA towers (``mla._towers`` / ``mla_forward``) and the expert FFN
(``moe.moe_forward`` / ``_moe_forward_ep``).

Sharding contract (why this works inside the manual-TP/SP ``shard_map``
executor with *no* kernel-side collectives): operands arrive pre-sharded.

* ``rmsnorm`` runs on the residual stream — replicated across TP, or the
  seq shard under SP; either way a plain (rows, h) problem per device.
* flash attention runs *inside* a TP region: the f/ğ entry operator has
  already gathered the full sequence, and the head dim is the TP-local
  ``n_h/tp`` — the kernel's (b·n_h_local, s) grid never sees a collective.
* ``grouped_mlp`` consumes the MoE dispatch buffer: ``(E, C, h)`` under
  ETP (ff-sharded weights, full capacity after the SP gather) or
  ``(E/ep, C_loc, h)`` under EP (expert-sharded weights, post-a2a rows).
  Capacity is static and rows are pre-grouped per expert, so the grouped
  GEMM's ``expert_map`` is the static ``repeat(arange(E), C/block_m)`` —
  no host-side regrouping (``pad_groups``) in the traced path.

Autodiff contract: ``pl.pallas_call`` has no general transpose rule, so
each pallas op is a ``jax.custom_vjp`` — forward through the kernel,
backward by re-deriving the vjp of the jnp oracle (``kernels.ref``) from
the saved *inputs*.  That is exactly the flash recompute story: nothing
O(s²) is resident between forward and backward; the score matrix only
materialises transiently inside one layer's backward.  It also pins the
gradients to the reference path, so the executor equivalence harnesses
compare like with like.
"""

from __future__ import annotations

import functools
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp

BACKENDS = ("reference", "pallas")
# attention impls that never materialise the resident 5·b·n_h·s² buffers
# (the memory model's attn_impl="flash" accounting — see
# core.activations.FLASH_ATTN_IMPLS, which must stay in sync)
FLASH_IMPLS = ("pallas", "flash")


# ---------------------------------------------------------------------------
# Backend / attention-impl resolution (replaces the ad-hoc use_pallas +
# attn_impl special cases that used to live in transformer.block_apply)
# ---------------------------------------------------------------------------

def resolve_backend(opts) -> str:
    """ModelOptions -> backend name.  ``use_pallas=True`` is the deprecated
    spelling of ``backend="pallas"``; ``opts=None`` means reference."""
    if opts is None:
        return "reference"
    backend = getattr(opts, "backend", "reference")
    if getattr(opts, "use_pallas", False):
        backend = "pallas"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")
    return backend


def attention_fallbacks(opts, *, causal: bool = True,
                        window: Optional[int] = None) -> List[str]:
    """Reasons the pallas flash kernel cannot serve this attention call,
    as human-readable strings (empty list = the fast path applies) — the
    ``core.notation.tp_violations``-style report for kernel dispatch."""
    if resolve_backend(opts) != "pallas":
        return []
    bad = []
    if not causal:
        bad.append("causal=False (flash kernel is causal-only)")
    if window is not None:
        bad.append(f"sliding_window={window} (flash kernel has no window mask)")
    return bad


def resolve_attn_impl(opts, *, causal: bool = True,
                      window: Optional[int] = None) -> str:
    """The attention impl a block should run: ``"pallas"`` when the backend
    is pallas and the kernel's contract holds, else ``opts.attn_impl`` —
    loudly, never silently (the old ``use_pallas and causal`` branch
    dropped to naive without a word)."""
    base = getattr(opts, "attn_impl", "naive") if opts is not None else "naive"
    if resolve_backend(opts) != "pallas":
        return base
    bad = attention_fallbacks(opts, causal=causal, window=window)
    if bad:
        warnings.warn(
            "backend='pallas': attention falling back to "
            f"'{base}' — {'; '.join(bad)}", RuntimeWarning, stacklevel=3)
        return base
    return "pallas"


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _pallas_rmsnorm(eps: float, gemma_style: bool, x, scale):
    from repro.kernels import ops as K
    return K.rmsnorm(x, scale, eps=eps, gemma_style=gemma_style)


def _pallas_rmsnorm_fwd(eps, gemma_style, x, scale):
    return _pallas_rmsnorm(eps, gemma_style, x, scale), (x, scale)


def _pallas_rmsnorm_bwd(eps, gemma_style, res, g):
    from repro.kernels.ref import rmsnorm_ref
    x, scale = res
    _, vjp = jax.vjp(
        lambda x_, s_: rmsnorm_ref(x_, s_, eps=eps, gemma_style=gemma_style),
        x, scale)
    return vjp(g)


_pallas_rmsnorm.defvjp(_pallas_rmsnorm_fwd, _pallas_rmsnorm_bwd)


def rmsnorm(p, x, eps: float = 1e-6, *, gemma_style: bool = False,
            backend: str = "reference"):
    """Backend-dispatched RMSNorm; same (params, x, eps) signature as
    ``layers.rmsnorm`` so call sites swap in place."""
    if backend == "pallas":
        return _pallas_rmsnorm(float(eps), bool(gemma_style), x, p["scale"])
    from .layers import rmsnorm as rmsnorm_jnp
    return rmsnorm_jnp(p, x, eps, gemma_style=gemma_style)


# ---------------------------------------------------------------------------
# attention (GQA and MLA share this: the kernel supports dq != dv)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_attention(scale: float, q, k, v):
    from repro.kernels import ops as K
    return K.flash_attention(q, k, v, scale=scale, causal=True)


def _pallas_attention_fwd(scale, q, k, v):
    return _pallas_attention(scale, q, k, v), (q, k, v)


def _pallas_attention_bwd(scale, res, g):
    # Recompute-style backward through the jnp oracle: only q/k/v were
    # saved, so the s² score matrix exists transiently inside this vjp and
    # is never resident across the forward/backward gap — the accounting
    # core.activations prices as attn_impl="flash".
    from repro.kernels.ref import flash_attention_ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, scale=scale,
                                               causal=True), q, k, v)
    return vjp(g)


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


def attention(q, k, v, *, scale: float, impl: str = "naive",
              causal: bool = True, window: Optional[int] = None):
    """Context for (b, s, n_h, d) heads — n_h is whatever the caller holds
    (the TP-local shard inside the executor).  ``impl``: "pallas"/"flash"
    (kernel), "chunked" (jnp online-softmax), anything else = naive.  An
    unsupported flash request falls back to naive with a RuntimeWarning
    naming the reason (never silently)."""
    if impl in FLASH_IMPLS:
        if causal and window is None:
            return _pallas_attention(float(scale), q, k, v)
        reasons = []
        if not causal:
            reasons.append("causal=False (flash kernel is causal-only)")
        if window is not None:
            reasons.append(f"sliding_window={window} "
                           "(flash kernel has no window mask)")
        warnings.warn(
            f"attention: impl={impl!r} unsupported here — "
            f"{'; '.join(reasons)}; falling back to naive",
            RuntimeWarning, stacklevel=2)
        impl = "naive"
    if impl == "chunked":
        from .attention import chunked_attention
        return chunked_attention(q, k, v, scale, window=window)
    from .attention import causal_mask, naive_attention
    s = q.shape[1]
    mask = causal_mask(s, window) if causal \
        else jnp.ones((s, k.shape[1]), bool)
    return naive_attention(q, k, v, mask, scale)


def mla_attention(q, k, v, *, scale: float, impl: str = "naive"):
    """MLA context (dq = d_h + d_hr, dv = d_v): same dispatch, causal-only,
    no sliding window — kept as its own name so call sites read as the
    paper's Figure 2."""
    return attention(q, k, v, scale=scale, impl=impl, causal=True)


# ---------------------------------------------------------------------------
# grouped MLP (the MoE expert FFN over the static-capacity dispatch buffer)
# ---------------------------------------------------------------------------

def _gmm_block(n: int, *, rows: bool, pref: int = 128) -> int:
    """Block size for one GEMM dim: the MXU-friendly 128 when it divides,
    else the whole dim as one tile.  A whole column tile (f or h) is
    always legal — it spans the array's full last dim.  A row block (the
    capacity C) is one of E·C rows, so Mosaic needs it 8-aligned; pick
    ``capacity_factor``, b and s so that C is a multiple of 128 (or at
    least 8) — anything else is refused here, before lowering."""
    if n % pref == 0:
        return pref
    if rows and n % 8:
        raise ValueError(
            f"grouped_mlp: capacity C={n} is neither a multiple of {pref} "
            "nor 8-aligned, so the Pallas row block cannot be C itself; "
            "choose capacity_factor, batch and seq so C % 128 == 0")
    return n


@jax.custom_vjp
def _pallas_grouped_mlp(buf, wg, wu, wd):
    from repro.kernels import ops as K
    E, C, h = buf.shape
    f = wg.shape[-1]
    bm = _gmm_block(C, rows=True)
    # rows are pre-grouped C-per-expert, so the expert map is static
    emap = jnp.repeat(jnp.arange(E, dtype=jnp.int32), C // bm)
    lhs = buf.reshape(E * C, h)
    bn_f, bn_h = _gmm_block(f, rows=False), _gmm_block(h, rows=False)
    gate = K.gmm(lhs, wg, emap, block_m=bm, block_n=bn_f)
    up = K.gmm(lhs, wu, emap, block_m=bm, block_n=bn_f)
    a = jax.nn.silu(gate) * up
    out = K.gmm(a, wd, emap, block_m=bm, block_n=bn_h)
    return out.reshape(E, C, h)


def _grouped_mlp_ref(buf, wg, wu, wd):
    a = jax.nn.silu(jnp.einsum("ech,ehf->ecf", buf, wg))
    a = a * jnp.einsum("ech,ehf->ecf", buf, wu)
    return jnp.einsum("ecf,efh->ech", a, wd)


def _pallas_grouped_mlp_fwd(buf, wg, wu, wd):
    return _pallas_grouped_mlp(buf, wg, wu, wd), (buf, wg, wu, wd)


def _pallas_grouped_mlp_bwd(res, g):
    _, vjp = jax.vjp(_grouped_mlp_ref, *res)
    return vjp(g)


_pallas_grouped_mlp.defvjp(_pallas_grouped_mlp_fwd, _pallas_grouped_mlp_bwd)


def grouped_mlp(buf, wg, wu, wd, *, backend: str = "reference"):
    """SwiGLU expert FFN batched over the expert dim.

    buf: (E, C, h) dispatch buffer (E and C are whatever the caller's
    parallelism left local — E/ep experts under EP, C·sp capacity after
    the SP gather); wg/wu: (E, h, f) with f possibly ff-sharded (ETP);
    wd: (E, f, h).  The pallas path runs three grouped GEMMs on the
    flattened (E·C, h) rows with a static expert map."""
    if backend == "pallas":
        return _pallas_grouped_mlp(buf, wg, wu, wd)
    return _grouped_mlp_ref(buf, wg, wu, wd)
