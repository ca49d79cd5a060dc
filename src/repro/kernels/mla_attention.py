"""Flash-style causal attention Pallas TPU kernels (MLA-shaped), forward and
backward.

The paper's dominant activation term is the 5·b·n_h·s² score/softmax family
(§5.1) — the tensor these kernels eliminate.  Online-softmax tiles keep the
working set at (block_q × block_k) in VMEM, so activation memory drops from
O(s²) to O(s), and the backward recomputes the probabilities block by block
from the forward's per-row log-sum-exp instead of reading them back.

MLA shape notes: q/k head dim = d_h + d_hr (192 for DeepSeek-v3), v head
dim = d_v (128) — both kernels support dq != dv.  GQA reuses them after
head replication.

Forward grid: (batch*heads, q_blocks); the kernel fori-loops over k blocks
up to the causal frontier carrying (m, l, acc) in VMEM, and writes the
output and ``lse = m + log(l)`` of its rows.

Backward (FlashAttention-2's, one fused kernel): grid (batch*heads,
k_blocks).  A k block loops over the q blocks at or below the causal
frontier, in the transposed orientation (rows are keys, so the row
statistics ``lse`` and ``delta = rowsum(dO ∘ O)`` broadcast along lanes):

    Sᵀ = K Qᵀ · scale      Pᵀ = exp(Sᵀ − lse)      dV += Pᵀ dO
    dPᵀ = V dOᵀ            dSᵀ = Pᵀ ∘ (dPᵀ − delta)
    dK += dSᵀ Q · scale    dQ[q block] += (dSᵀ)ᵀ K · scale

dK and dV accumulate in VMEM over the q loop and leave with their k block;
dQ accumulates in a whole-sequence VMEM buffer of the head and leaves after
its last k block.  The matmuls take the inputs' dtype (P and dS are cast to
it, as FlashAttention-2 does) and accumulate in float32.  Only the blocks
that straddle the diagonal are masked; fully masked ones are never visited.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
# contract the last dims of both operands: A @ Bᵀ
_NT = (((1,), (1,)), ((), ()))
# VMEM the backward may plan for (v5e holds 128 MiB)
_BWD_VMEM_BUDGET = 96 * 2 ** 20


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                  block_k: int, scale: float, seq_len: int, causal: bool):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # (block_q, dq)
    dv = v_ref.shape[-1]

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    n_kb = seq_len // block_k
    hi = jax.lax.min(((qi + 1) * block_q + block_k - 1) // block_k, n_kb) \
        if causal else n_kb

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                     # (block_q, block_k)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-20)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # the row statistics leave as a lane-major row (1, block_q)
    lse_ref[...] = jnp.transpose(
        jnp.broadcast_to(m + jnp.log(l), (block_q, 128)))[:1]


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           scale: float, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q/k: (b, s, n_h, dq); v: (b, s, n_h, dv) -> (o, lse): o (b, s, n_h,
    dv) and the float32 log-sum-exp of each row's scaled scores,
    (b, n_h, s) — what the backward recomputes the probabilities from.

    s is padded to a block multiple internally; causal masking makes the
    padding inert for the valid rows.
    """
    b, s, nh, dq = q.shape
    dv = v.shape[-1]
    bq = min(block_q, s)
    bk = min(block_k, s)
    n_qb = -(-s // bq)
    s_pad = n_qb * bq
    # unify q/k padding to one padded length divisible by both blocks
    s_pad = -(-s_pad // bk) * bk
    n_qb = s_pad // bq
    if s_pad != s:
        padder = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        q = jnp.pad(q, padder)
        k = jnp.pad(k, padder)
        v = jnp.pad(v, padder)

    # fold batch & heads: (b*nh, s_pad, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * nh, s_pad, dq)
    kf = k.transpose(0, 2, 1, 3).reshape(b * nh, s_pad, dq)
    vf = v.transpose(0, 2, 1, 3).reshape(b * nh, s_pad, dv)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=bq, block_k=bk, scale=scale,
                          seq_len=s_pad, causal=causal),
        grid=(b * nh, n_qb),
        in_specs=[
            pl.BlockSpec((None, bq, dq), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, s_pad, dq), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((None, s_pad, dv), lambda h, i: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, dv), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda h, i: (h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, s_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b * nh, 1, s_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, nh, s_pad, dv).transpose(0, 2, 1, 3)
    return out[:, :s], lse.reshape(b, nh, s_pad)[:, :, :s]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a (rows, cols) VMEM buffer in (8·4/itemsize, 128) tiles."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _bwd_plan(s: int, dq: int, dv: int, itemsize: int
              ) -> Tuple[int, int, int, int]:
    """(s_pad, block_q, block_k, vmem bytes) of the backward for one head
    of length ``s`` with these head dims.

    The sequence pads to a multiple of 128 (of 8 below 128, as one
    block).  A head's q, dO, lse, delta and dQ stay whole in VMEM (double
    buffered from one head to the next, plus dQ's float32 accumulator);
    k, v, dK and dV come and go in k blocks.  The q block is 256 rows
    where 256 divides s_pad, else 128; the k block is the largest of 512,
    256 and 128 that divides s_pad and fits the budget with the
    (block_k, block_q) float32 temporaries.  (On a v5e, 256 × 512 ran the
    backward fastest at s 2048 and 4096, dq 128 and 192.)
    """
    if s < 128:
        s_pad = bq = -(-s // 8) * 8
        cands = (s_pad,)
    else:
        s_pad = -(-s // 128) * 128
        bq = 256 if s_pad % 256 == 0 else 128
        cands = tuple(c for c in (512, 256, 128) if s_pad % c == 0)
    whole = 2 * (_vmem_bytes(s_pad, dq, itemsize)           # q
                 + _vmem_bytes(s_pad, dv, itemsize)         # dO
                 + _vmem_bytes(s_pad, dq, itemsize)         # dQ
                 + 2 * _vmem_bytes(1, s_pad, 4)) \
        + _vmem_bytes(s_pad, dq, 4)                         # dQ accumulator
    for bk in cands:
        per_block = 4 * (_vmem_bytes(bk, dq, itemsize)      # k, dK
                         + _vmem_bytes(bk, dv, itemsize)) \
            + _vmem_bytes(bk, dq, 4) + _vmem_bytes(bk, dv, 4) \
            + 8 * _vmem_bytes(bk, bq, 4)
        if whole + per_block <= _BWD_VMEM_BUDGET:
            return s_pad, bq, bk, whole + per_block
    raise ValueError(
        f"flash attention backward: a head of s={s}, dq={dq}, dv={dv} does "
        f"not fit {_BWD_VMEM_BUDGET >> 20} MiB of VMEM")


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      block_q: int, block_k: int, scale: float):
    kb = pl.program_id(1)
    n_qb = q_ref.shape[0] // block_q

    @pl.when(kb == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    k = k_ref[...]                                      # (block_k, dq)
    v = v_ref[...]                                      # (block_k, dv)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)

    def body(qb, masked):
        rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
        q = q_ref[rows, :]
        do = do_ref[rows, :]
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[:, rows])             # (block_k, block_q)
        dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[:, rows])
        dk_acc[...] += jnp.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
        dq_acc[rows, :] += jnp.dot(dst.T.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    # q blocks below the frontier see no key of this block; those up to
    # `full` straddle the diagonal
    lo = kb * block_k // block_q
    full = jax.lax.min(((kb + 1) * block_k + block_q - 1) // block_q, n_qb)
    jax.lax.fori_loop(lo, full, lambda i, c: body(i, True), None)
    jax.lax.fori_loop(full, n_qb, lambda i, c: body(i, False), None)
    dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd_pallas(q: jnp.ndarray, k: jnp.ndarray,
                               v: jnp.ndarray, o: jnp.ndarray,
                               lse: jnp.ndarray, do: jnp.ndarray, *,
                               scale: float, interpret: bool = False
                               ) -> Tuple[jnp.ndarray, ...]:
    """Causal attention's (dq, dk, dv) from the forward's inputs, its
    output ``o``, its ``lse`` (b, n_h, s) and the output's cotangent
    ``do``; shapes as ``flash_attention_pallas``'s."""
    b, s, nh, dq = q.shape
    dv = v.shape[-1]
    s_pad, bq, bk, vmem = _bwd_plan(s, dq, dv, q.dtype.itemsize)
    # delta = rowsum(dO ∘ O), the softmax's backward per row
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       o.astype(jnp.float32))

    def fold(x):                        # (b, s, nh, d) -> (b*nh, s_pad, d)
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(b * nh, s_pad, x.shape[-1])

    def rows(x):                        # (b, nh, s) -> (b*nh, 1, s_pad)
        return jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s))).reshape(
            b * nh, 1, s_pad)

    head = lambda d: pl.BlockSpec((None, s_pad, d), lambda h, j: (h, 0, 0))
    block = lambda d: pl.BlockSpec((None, bk, d), lambda h, j: (h, j, 0))
    row = pl.BlockSpec((None, 1, s_pad), lambda h, j: (h, 0, 0))
    dqf, dkf, dvf = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=bq, block_k=bk,
                          scale=scale),
        grid=(b * nh, s_pad // bk),
        in_specs=[head(dq), block(dq), block(dv), head(dv), row, row],
        out_specs=[head(dq), block(dq), block(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, s_pad, dq), q.dtype),
            jax.ShapeDtypeStruct((b * nh, s_pad, dq), k.dtype),
            jax.ShapeDtypeStruct((b * nh, s_pad, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((s_pad, dq), jnp.float32),
            pltpu.VMEM((bk, dq), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem + 8 * 2 ** 20),
        interpret=interpret,
    )(fold(q), fold(k), fold(v), fold(do), rows(lse), rows(delta))

    def unfold(x):
        return x.reshape(b, nh, s_pad, x.shape[-1]).transpose(0, 2, 1, 3)[:, :s]

    return unfold(dqf), unfold(dkf), unfold(dvf)
