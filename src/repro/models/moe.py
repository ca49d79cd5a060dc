"""Mixture-of-experts layer (paper §1.1/§3.3/§5.2).

Capacity-based token dispatch, built from sort/scatter primitives so the
per-device expert buffer is (E, C, h) — shardable on the expert axis (EP over
the mesh's ``model`` axis) — rather than the (T, E, C) one-hot einsum of
GShard, which is infeasible at long sequence lengths.

Matches the paper's accounting: balanced load gives E_token = b·s·N_r/N
tokens per expert (capacity_factor=1.0 reproduces §5.2 exactly; default 1.25
gives headroom like production routers).  Shared experts process every token
and are replicated across EP ranks (paper §3.3).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.notation import ModelSpec
from .layers import Params, dense_init, mlp_apply, mlp_init


class MoEOutput(NamedTuple):
    y: jnp.ndarray
    aux_loss: jnp.ndarray       # load-balance auxiliary loss
    # (T, E) fp32 normalised router probabilities (paper keeps 4bsN router
    # acts).  T is the *routed* token set: the full batch on the replicated
    # paths, the rank's own disjoint token chunk inside token-sharded
    # executors (SP and/or EP) — consumers wanting global stats must gather
    # over the token-sharding axis.
    router_probs: jnp.ndarray


def moe_init(key: jax.Array, spec: ModelSpec, dtype=jnp.bfloat16) -> Params:
    e = spec.moe
    kr, ke, ks = jax.random.split(key, 3)
    kg, ku, kd = jax.random.split(ke, 3)
    E, h, f = e.n_routed, spec.h, e.d_ff_expert
    p = {
        "router": dense_init(kr, (h, E), jnp.float32, scale=h ** -0.5),
        # stacked expert weights: leading dim = expert (EP-sharded)
        "we_gate": dense_init(kg, (E, h, f), dtype),
        "we_up": dense_init(ku, (E, h, f), dtype),
        "we_down": dense_init(kd, (E, f, h), dtype),
    }
    if e.n_shared:
        p["shared"] = mlp_init(ks, spec, f * e.n_shared, dtype)
    return p


def _route(router_w: jnp.ndarray, spec: ModelSpec, xt: jnp.ndarray,
           router_impl: str) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Route flat tokens (T, h) -> (probs (T, E) fp32, gates (T, K) fp32,
    eids (T, K) int32).  DeepSeek-v3 sigmoid scoring + top-k renorm, or
    classic top-k softmax (OLMoE/Qwen3).  Shared by the scatter, EP-a2a and
    GSPMD-a2a dispatch paths so routing can never drift between them."""
    e = spec.moe
    logits = xt.astype(jnp.float32) @ router_w
    if router_impl == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gate_vals, eids = jax.lax.top_k(scores, e.n_active)
        gates = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20)
        probs = scores / (scores.sum(-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eids = jax.lax.top_k(probs, e.n_active)
        gates = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20)
    return probs, gates, eids


def _send_eid_buffer(dest: jnp.ndarray, pos: jnp.ndarray,
                     local_eid: jnp.ndarray, n_dest: int, c_send: int,
                     e_loc: int) -> jnp.ndarray:
    """(n_dest, c_send) int32 buffer of local expert ids for the a2a send
    step; slots no kept assignment wrote carry ``e_loc``, the padding
    marker the receiver masks on.  ``pos`` is the UNCLAMPED rank of each
    assignment within its destination bucket: out-of-capacity assignments
    index past ``c_send`` and the scatter drops them (``mode="drop"``).
    Clamping them to ``c_send - 1`` instead — and writing the marker there
    — collided with the slot's real write (scatter-set with duplicate
    indices keeps an arbitrary one), so on bucket overflow a *kept*
    token's expert id could be overwritten by the marker and its expert
    output silently zeroed."""
    return jnp.full((n_dest, c_send), e_loc, jnp.int32) \
        .at[dest, pos].set(local_eid, mode="drop")


def _positions_in_expert(eids: jnp.ndarray, n_expert: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """For flat expert assignments (TK,), compute each assignment's rank
    within its expert and the per-expert totals.

    Sort-based: O(TK log TK) compares.  (A (TK, E) one-hot cumsum is the
    obvious alternative but XLA lowers it to a reduce-window that both
    costs and *counts* O(TK²·E) — it dominated the roofline compute term
    100× over the expert matmuls before this change; see EXPERIMENTS.md
    §Perf iteration log.)"""
    tk = eids.shape[0]
    order = jnp.argsort(eids, stable=True)
    sorted_eids = eids[order]
    counts = jnp.zeros((n_expert,), jnp.int32).at[eids].add(1)
    offsets = jnp.cumsum(counts) - counts              # (E,) group starts
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - offsets[sorted_eids]
    pos = jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted)
    return pos, counts


def moe_forward(p: Params, spec: ModelSpec, x: jnp.ndarray, *,
                capacity_factor: float = 1.25,
                router_impl: str = "softmax",
                tp_f=None, tp_g=None,
                sp_axis: Optional[str] = None,
                ep: int = 1,
                ep_axis: Optional[str] = None,
                dp_axes: Tuple[str, ...] = (),
                backend: str = "reference") -> MoEOutput:
    """x: (b, s, h) -> (b, s, h).

    DeepSeek-v3 uses sigmoid scoring + top-k renormalisation; classic top-k
    softmax also supported (OLMoE/Qwen3 use softmax).

    ``tp_f``/``tp_g`` (optional) are the pipeline executor's manual
    tensor-parallel entry/exit operators (``parallel.tp``): expert weights
    arrive sharded on their *ff* dim (ETP — every shard holds all experts,
    1/tp of each expert's hidden), the router/dispatch runs replicated and
    bit-identical on every shard, ``tp_f`` wraps the dispatch buffer and
    shared-expert input, ``tp_g`` sums the partial expert outputs.  The
    returned ``y`` and ``aux_loss`` are then replicated across TP.

    ``sp_axis`` marks the executor's sequence-parallel mode: ``x`` is a
    *seq shard* (each TP rank routes and dispatches its own disjoint token
    chunk — the router activations live 1/sp per shard), ``tp_f`` is then
    the ğ all-gather whose token dim for the (E, C, h) dispatch buffer is
    its capacity dim, so the expert FFN still sees every shard's tokens,
    and ``tp_g`` reduce-scatters each shard its own tokens' outputs.  The
    load-balance means are combined across shards (``pmean_sp``) before
    the aux product — per-shard token sets are disjoint and equal-sized,
    so the combined aux equals the sp=1 value exactly; the resulting
    seq-partial router gradient is completed by the executor's post-loop
    'model'-axis psum.

    ``ep``/``ep_axis`` (paper §3.3) switch the routed experts to true
    expert parallelism over ``ep_axis`` (the executor's 'model' axis,
    ``ep`` == its size): expert weights arrive sharded on their *expert*
    dim (``(E/ep, h, h_E)`` per rank, full hidden), each rank routes its
    own disjoint token chunk — the seq shard under SP, an explicit
    ``shard_tokens_ep`` slice of the replicated residual otherwise — and
    the dispatch is :func:`_moe_dispatch_ep`'s send-bucket / all-to-all /
    local grouped FFN / all-to-all-back exchange.  The shared expert stays
    on the ETP path (``tp_f``/``tp_g``, every token), and the router —
    consumed inside the token-sharded region — accumulates token-partial
    gradients the executor completes with its post-loop 'model' psum
    (the same completion SP already requires).

    ``dp_axes`` names the data-parallel mesh axes whose shards hold the
    other samples of the same microbatch.  The load-balance means are
    combined across them too, so ``aux_loss`` is the whole-microbatch value
    of the unsharded step (a per-shard aux would be a mean of products, not
    the product of the means).  The router gradient this leaves is
    data-partial, which the executor's post-loop data psum completes."""
    e = spec.moe
    b, s, h = x.shape
    T = b * s
    E, K = e.n_routed, e.n_active
    xt = x.reshape(T, h)

    if ep > 1:
        if ep_axis is None:
            raise ValueError("moe_forward: ep > 1 needs ep_axis (the mesh "
                             "axis the a2a dispatch group lives on)")
        if E % ep:
            raise ValueError(f"ep={ep} does not divide n_routed={E}")
        return _moe_forward_ep(p, spec, x, capacity_factor=capacity_factor,
                               router_impl=router_impl, tp_f=tp_f, tp_g=tp_g,
                               sp_axis=sp_axis, ep=ep, ep_axis=ep_axis,
                               dp_axes=dp_axes, backend=backend)

    probs, gates, eids = _route(p["router"], spec, xt, router_impl)

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(eids, E, dtype=jnp.float32).sum(1)), axis=0) / K
    stat_axes = tuple(dp_axes) + ((sp_axis,) if sp_axis is not None else ())
    if stat_axes:
        from repro.parallel.tp import pmean_sp
        me, ce = pmean_sp(me, stat_axes), pmean_sp(ce, stat_axes)
    aux = E * jnp.sum(me * ce)

    C = int(max(1, round(T * K / E * capacity_factor)))
    flat_eids = eids.reshape(T * K)
    pos, _ = _positions_in_expert(flat_eids, E)
    keep = (pos < C)
    pos_c = jnp.minimum(pos, C - 1)

    # dispatch: scatter kept tokens into the (E, C, h) buffer (EP-sharded)
    src = jnp.repeat(xt, K, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E, C, h), x.dtype).at[flat_eids, pos_c].add(src)
    if tp_f is not None:
        buf = tp_f(buf)

    # expert FFN (SwiGLU), batched over the expert dim — the backend's
    # grouped_mlp (pallas: three grouped GEMMs over the flattened
    # static-capacity rows; reference: the einsum triple)
    from .backend import grouped_mlp
    out_buf = grouped_mlp(buf, p["we_gate"], p["we_up"], p["we_down"],
                          backend=backend)
    if tp_g is not None:
        out_buf = tp_g(out_buf)

    # combine: gather each assignment's expert output, weight, sum over K
    y_pairs = out_buf[flat_eids, pos_c] * (gates.reshape(T * K)
                                           * keep.astype(jnp.float32)
                                           )[:, None].astype(x.dtype)
    y = y_pairs.reshape(T, K, h).sum(axis=1)

    if e.n_shared:
        xs = tp_f(xt) if tp_f is not None else xt
        ys = mlp_apply(p["shared"], spec, xs)
        y = y + (tp_g(ys) if tp_g is not None else ys)
    return MoEOutput(y=y.reshape(b, s, h), aux_loss=aux, router_probs=probs)


def _moe_forward_ep(p: Params, spec: ModelSpec, x: jnp.ndarray, *,
                    capacity_factor: float, router_impl: str,
                    tp_f, tp_g, sp_axis: Optional[str],
                    ep: int, ep_axis: str,
                    dp_axes: Tuple[str, ...] = (),
                    backend: str = "reference") -> MoEOutput:
    """True expert parallelism inside the manual-collectives executor
    (paper §3.3): weights sharded ``(E/ep, h, h_E)`` on the expert dim over
    ``ep_axis``, token exchange via two ``lax.all_to_all``\\ s.

    Per rank: route the rank's own disjoint token chunk (the seq shard
    under SP; a ``shard_tokens_ep`` slice of the replicated residual
    otherwise), bucket assignments by destination expert shard
    (``dest = eid // (E/ep)``, capacity ``C_send = tk/ep·cf`` applied
    *once*), a2a the ``(ep, C_send, h)`` send buffer, run the local
    ``(E/ep, C, h)`` grouped FFN — ``C`` is the same global per-expert
    capacity as ep=1, so the buffer is exactly the analytic ``/ep``
    dispatch term — then a2a the outputs back and combine with the
    locally-kept gates.  The router is consumed inside the token-sharded
    region, so its local gradient is token-partial; the executor's
    post-loop 'model' psum completes it (``train.pipeline_loop``)."""
    from repro.parallel.tp import (pmean_sp, shard_tokens_ep,
                                   unshard_tokens_ep)
    e = spec.moe
    b, s, h = x.shape
    E, K = e.n_routed, e.n_active
    E_loc = E // ep
    xt_full = x.reshape(b * s, h)
    if sp_axis is None:
        if (b * s) % ep:
            raise ValueError(
                f"ep={ep} does not divide the per-rank token count "
                f"{b * s}; the EP token slice has no pad fallback")
        xt = shard_tokens_ep(xt_full, ep_axis, 0)
    else:
        xt = xt_full            # SP residual is already the token shard
    t_loc = xt.shape[0]

    probs, gates, eids = _route(p["router"], spec, xt, router_impl)
    # per-chunk token sets are disjoint and equal-sized: the pmean of the
    # per-chunk means is the exact global mean, so aux == the ep=1 value
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(eids, E, dtype=jnp.float32).sum(1)), axis=0) / K
    stat_axes = tuple(dp_axes) + (ep_axis,)
    me, ce = pmean_sp(me, stat_axes), pmean_sp(ce, stat_axes)
    aux = E * jnp.sum(me * ce)

    tk = t_loc * K
    flat_eids = eids.reshape(tk)
    flat_gates = gates.reshape(tk)
    dest = flat_eids // E_loc
    local_eid = flat_eids % E_loc

    # send: bucket by destination shard, capacity_factor applied once here
    c_send = int(max(1, round(tk / ep * capacity_factor)))
    pos_d, _ = _positions_in_expert(dest, ep)
    keep_s = pos_d < c_send
    pos_dc = jnp.minimum(pos_d, c_send - 1)
    src = jnp.repeat(xt, K, axis=0) * keep_s[:, None].astype(x.dtype)
    send = jnp.zeros((ep, c_send, h), x.dtype).at[dest, pos_dc].add(src)
    send_eid = _send_eid_buffer(dest, pos_d, local_eid, ep, c_send, E_loc)

    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                              concat_axis=0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)

    # Dual-stream shape: the shared expert depends only on the residual,
    # not on the a2a payloads, so it is computed *between* the dispatch
    # a2a's issue and its first consumer — XLA's scheduler is free to run
    # the ETP matmuls while the token exchange is in flight (the DualPipe
    # overlap structure at slot granularity).
    ys = None
    if e.n_shared:
        xs = tp_f(xt_full) if tp_f is not None else xt_full
        ys = mlp_apply(p["shared"], spec, xs)
        if tp_g is not None:
            ys = tp_g(ys)

    # local grouped FFN over the (E/ep, C, h) buffer; C = the global
    # per-expert capacity (tk·ep assignments over E experts), NOT scaled
    # by capacity_factor a second time
    rows = recv.reshape(ep * c_send, h)
    row_eid = recv_eid.reshape(ep * c_send)
    pos_e, _ = _positions_in_expert(row_eid, E_loc + 1)
    c_loc = int(max(1, round(tk * ep / E * capacity_factor)))
    keep_e = (pos_e < c_loc) & (row_eid < E_loc)
    pos_ec = jnp.minimum(pos_e, c_loc - 1)
    eid_c = jnp.minimum(row_eid, E_loc - 1)
    buf = jnp.zeros((E_loc, c_loc, h), x.dtype) \
        .at[eid_c, pos_ec].add(rows * keep_e[:, None].astype(x.dtype))

    # local grouped FFN on the (E/ep, C, h) post-a2a buffer — the EP shard
    # the pallas grouped GEMM sees (expert-dim-sharded weights, full hidden)
    from .backend import grouped_mlp
    out_buf = grouped_mlp(buf, p["we_gate"], p["we_up"], p["we_down"],
                          backend=backend)

    back = (out_buf[eid_c, pos_ec] * keep_e[:, None].astype(x.dtype)) \
        .reshape(ep, c_send, h)
    ret = jax.lax.all_to_all(back, ep_axis, split_axis=0,
                             concat_axis=0, tiled=False)

    y_pairs = ret[dest, pos_dc] * (flat_gates * keep_s.astype(jnp.float32)
                                   )[:, None].astype(x.dtype)
    y = y_pairs.reshape(t_loc, K, h).sum(axis=1)
    if sp_axis is None:
        y = unshard_tokens_ep(y, ep_axis, 0)       # rejoin replicated stream

    if ys is not None:
        # shared experts process every token and stay on the ETP path
        y = y + ys
    # probs are the rank's token chunk only (documented: per-shard under EP)
    return MoEOutput(y=y.reshape(b, s, h), aux_loss=aux, router_probs=probs)


def moe_forward_dense_ref(p: Params, spec: ModelSpec, x: jnp.ndarray, *,
                          router_impl: str = "softmax") -> jnp.ndarray:
    """Dropless dense reference: every token runs through its top-k experts
    via full (T, E) weighting.  O(T·E·h·f) — for tests on tiny sizes only."""
    e = spec.moe
    b, s, h = x.shape
    T = b * s
    xt = x.reshape(T, h)
    logits = xt.astype(jnp.float32) @ p["router"]
    if router_impl == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        gate_vals, eids = jax.lax.top_k(scores, e.n_active)
        gates = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eids = jax.lax.top_k(probs, e.n_active)
        gates = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20)
    w = jnp.zeros((T, e.n_routed), jnp.float32)
    w = w.at[jnp.arange(T)[:, None], eids].set(gates)
    # per-expert dense pass
    a = jax.nn.silu(jnp.einsum("th,ehf->etf", xt, p["we_gate"]))
    a = a * jnp.einsum("th,ehf->etf", xt, p["we_up"])
    ye = jnp.einsum("etf,efh->eth", a, p["we_down"])       # (E, T, h)
    y = jnp.einsum("te,eth->th", w.astype(x.dtype), ye)
    if e.n_shared:
        y = y + mlp_apply(p["shared"], spec, xt)
    return y.reshape(b, s, h)
