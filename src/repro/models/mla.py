"""Multi-Head Latent Attention (DeepSeek-v2/v3), paper §1/§3.2/§5.1.

Training path mirrors Figure 2's activation pattern: query tower
(W^DQ → norm → W^UQ/W^QR, or one W^Q where the model has no query LoRA,
as DeepSeek-V2-Lite), latent KV (W^DKV → norm → W^UK/W^UV), shared rope
key W^KR, softmax over concat(nope, rope) dims, W^O out.  Under YaRN rope
scaling the rotary frequencies are YaRN's and the softmax scale carries
its ``mscale²``.

Decode path caches the *compressed* latent (d_c + d_hr per token — the MLA
memory advantage the paper's Table 2 geometry implies) and absorbs W^UK into
the query so scores contract in the 512-dim latent space.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro import scopes as S
from repro.core.notation import ModelSpec
from .layers import (Params, apply_rope, dense_init, rmsnorm, rmsnorm_init,
                     yarn_mscale)

NEG_INF = -2.0 ** 30


def mla_init(key: jax.Array, spec: ModelSpec, dtype=jnp.bfloat16) -> Params:
    m = spec.mla
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": dense_init(ks[3], (spec.h, m.d_c), dtype),
        "w_uk": dense_init(ks[4], (m.d_c, spec.n_h * m.d_h), dtype),
        "w_uv": dense_init(ks[5], (m.d_c, spec.n_h * m.d_v), dtype),
        "w_kr": dense_init(ks[6], (spec.h, m.d_hr), dtype),
        "w_o": dense_init(ks[7], (spec.n_h * m.d_v, spec.h), dtype),
        "kv_norm": rmsnorm_init(m.d_c, dtype),
    }
    if m.d_cq is None:
        # each head's query as (nope ‖ rope) columns, as the checkpoint's
        # q_proj lays them out
        p["w_q"] = dense_init(ks[0], (spec.h, spec.n_h * (m.d_h + m.d_hr)),
                              dtype)
    else:
        p.update(w_dq=dense_init(ks[0], (spec.h, m.d_cq), dtype),
                 w_uq=dense_init(ks[1], (m.d_cq, spec.n_h * m.d_h), dtype),
                 w_qr=dense_init(ks[2], (m.d_cq, spec.n_h * m.d_hr), dtype),
                 q_norm=rmsnorm_init(m.d_cq, dtype))
    return p


def softmax_scale(spec: ModelSpec) -> float:
    """``(d_h + d_hr)^-½``, times YaRN's ``mscale²`` where the rope is
    scaled (DeepSeek-V2's attention)."""
    m, y = spec.mla, spec.yarn
    scale = (m.d_h + m.d_hr) ** -0.5
    if y is not None:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _queries(p: Params, spec: ModelSpec, x: jnp.ndarray, qf,
             backend: str = "reference"):
    """(q_nope, q_rope before rope) of ``x`` (b, s, h): through the query
    latent (W^DQ → norm → W^UQ/W^QR), or W^Q where there is none.  ``qf``
    is applied where the replicated input fans out into head-sharded
    projections (the latent, or ``x`` itself)."""
    from . import backend as B
    m = spec.mla
    b, s, _ = x.shape
    if m.d_cq is None:
        q = (qf(x) @ p["w_q"]).reshape(b, s, spec.n_h, m.d_h + m.d_hr)
        return q[..., :m.d_h], q[..., m.d_h:]
    cq = qf(B.rmsnorm(p["q_norm"], x @ p["w_dq"], spec.norm_eps,
                      backend=backend))
    return ((cq @ p["w_uq"]).reshape(b, s, spec.n_h, m.d_h),
            (cq @ p["w_qr"]).reshape(b, s, spec.n_h, m.d_hr))


def _towers(p: Params, spec: ModelSpec, x: jnp.ndarray,
            positions: jnp.ndarray, tpf=None, backend: str = "reference"):
    """Shared by train fwd and prefill: returns q (nope‖rope), k (nope‖rope), v.

    ``tpf`` (optional) is the executor's tensor-parallel entry operator
    (``parallel.tp.copy_to_tp``): the down-projections W^DQ/W^DKV/W^KR are
    replicated across TP (paper §3.2) and computed redundantly on every
    shard, so the compressed latents — the points where the replicated
    towers fan out into head-sharded up-projections — are where the
    backward pass must all-reduce.

    Under the executor's sequence parallelism the caller gathers the
    seq-sharded block input *before* these towers
    (``models.pipeline._slot_apply``): the replicated latent towers always
    consume the full-sequence view, so cq/c_kv stay ``2bs(d_cq+d_c)`` per
    shard — the terms the paper's Table 10 leaves undivided by sp — but
    ``tpf`` must then be ``None``: the entry ğ's reduce-scatter backward
    already sums the per-shard partial cotangents, so keeping
    ``copy_to_tp``'s psum-bwd here would double-count (tp× gradients on
    the whole attention branch).  The tower weight grads are instead
    completed by the executor's post-loop 'model'-axis psum.
    """
    from . import backend as B
    m = spec.mla
    b, s, _ = x.shape
    tpf = tpf if tpf is not None else (lambda t: t)
    with jax.named_scope(S.MLA_TOWERS):
        q_nope, q_rope = _queries(p, spec, x, tpf, backend)
        q_rope = apply_rope(q_rope, positions, spec.rope_theta, spec.yarn)
        c_kv = tpf(B.rmsnorm(p["kv_norm"], x @ p["w_dkv"], spec.norm_eps,
                             backend=backend))
        k_nope = (c_kv @ p["w_uk"]).reshape(b, s, spec.n_h, m.d_h)
        k_rope = apply_rope((x @ p["w_kr"]).reshape(b, s, 1, m.d_hr),
                            positions, spec.rope_theta, spec.yarn)
        k_rope = jnp.broadcast_to(tpf(k_rope), (b, s, spec.n_h, m.d_hr))
        v = (c_kv @ p["w_uv"]).reshape(b, s, spec.n_h, m.d_v)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return q, k, v


def mla_forward(p: Params, spec: ModelSpec, x: jnp.ndarray,
                positions: jnp.ndarray, *, impl: str = "naive",
                tpf=None, backend: str = "reference") -> jnp.ndarray:
    from . import backend as B
    m = spec.mla
    b, s, _ = x.shape
    q, k, v = _towers(p, spec, x, positions, tpf, backend=backend)
    ctx = B.mla_attention(q, k, v, scale=softmax_scale(spec), impl=impl)
    return ctx.reshape(b, s, spec.n_h * m.d_v) @ p["w_o"]


# ---------------------------------------------------------------------------
# Decode with compressed-latent cache
# ---------------------------------------------------------------------------

def init_mla_cache(spec: ModelSpec, n_layers: int, b: int, cache_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    m = spec.mla
    return {
        "c_kv": jnp.zeros((n_layers, b, cache_len, m.d_c), dtype),
        "k_rope": jnp.zeros((n_layers, b, cache_len, m.d_hr), dtype),
    }


def mla_decode(p: Params, spec: ModelSpec, x: jnp.ndarray,
               c_cache: jnp.ndarray, r_cache: jnp.ndarray,
               index: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token MLA decode with latent cache.

    x: (b, 1, h);  c_cache: (b, C, d_c);  r_cache: (b, C, d_hr);  index: ().
    Scores via weight absorption: q_eff = W^UKᵀ q_nope contracts against the
    cached latent directly; values reconstructed as (probs @ c) W^UV.
    """
    m = spec.mla
    b = x.shape[0]
    cache_len = c_cache.shape[1]
    pos = jnp.full((b, 1), index, jnp.int32)

    q_nope, q_rope = _queries(p, spec, x, lambda t: t)
    q_rope = apply_rope(q_rope, pos, spec.rope_theta, spec.yarn)

    c_new = rmsnorm(p["kv_norm"], x @ p["w_dkv"], spec.norm_eps)   # (b,1,d_c)
    r_new = apply_rope((x @ p["w_kr"]).reshape(b, 1, 1, m.d_hr),
                       pos, spec.rope_theta, spec.yarn).reshape(b, 1, m.d_hr)
    c_cache = jax.lax.dynamic_update_slice_in_dim(c_cache, c_new, index, axis=1)
    r_cache = jax.lax.dynamic_update_slice_in_dim(r_cache, r_new, index, axis=1)

    # absorb W^UK: (b,1,nh,d_h) x (d_c, nh*d_h) -> (b,1,nh,d_c)
    w_uk = p["w_uk"].reshape(m.d_c, spec.n_h, m.d_h)
    q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, w_uk)
    s_nope = jnp.einsum("bqhc,bkc->bhqk", q_lat, c_cache)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, r_cache)
    scores = (s_nope + s_rope).astype(jnp.float32) * softmax_scale(spec)
    valid = jnp.arange(cache_len) <= index
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)

    ctx_lat = jnp.einsum("bhqk,bkc->bqhc", probs, c_cache)         # (b,1,nh,d_c)
    w_uv = p["w_uv"].reshape(m.d_c, spec.n_h, m.d_v)
    ctx = jnp.einsum("bqhc,chd->bqhd", ctx_lat, w_uv)
    out = ctx.reshape(b, 1, spec.n_h * m.d_v) @ p["w_o"]
    return out, c_cache, r_cache
