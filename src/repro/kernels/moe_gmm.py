"""Grouped expert matmul (GMM) Pallas TPU kernel.

The MoE hot loop (paper §3.3/§5.2): after dispatch, each expert multiplies
its token slab by its own weights.  A loop of per-expert matmuls wastes MXU
time on small ragged groups; the megablox-style GMM walks one (M, K)×(E, K,
N) problem where rows are grouped by expert, with the row-block → expert map
prefetched to SMEM so each grid step loads the right expert's weight tile.

Caller contract: rows pre-sorted by expert, each group padded to a multiple
of block_m (``pad_groups`` below does both).  Tiles are MXU-aligned
(block_m × block_n = 128×128 default, K kept whole in VMEM — h_E=2048 and
h=7168 tiles fit comfortably: 128·7168·2B ≈ 1.8 MiB).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(expert_map_ref, lhs_ref, rhs_ref, out_ref):
    # expert_map is scalar-prefetched; BlockSpec index_maps already selected
    # the right expert tile of rhs, so the body is a plain MXU matmul.
    out_ref[...] = jnp.dot(
        lhs_ref[...].astype(jnp.float32), rhs_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def gmm_pallas(lhs: jnp.ndarray, rhs: jnp.ndarray, expert_map: jnp.ndarray,
               *, block_m: int = 128, block_n: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """lhs: (M, K) rows grouped by expert; rhs: (E, K, N);
    expert_map: (M//block_m,) int32 — expert id of each row block.
    Returns (M, N)."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2, (K, K2)
    assert M % block_m == 0, "pad groups to block_m first"
    bn = min(block_n, N)
    assert N % bn == 0
    grid = (M // block_m, N // bn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j, emap: (i, 0)),
            pl.BlockSpec((None, K, bn), lambda i, j, emap: (emap[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, emap: (i, j)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=interpret,
    )(expert_map, lhs, rhs)


def pad_groups(x: jnp.ndarray, group_sizes: np.ndarray, block_m: int
               ) -> Tuple[jnp.ndarray, np.ndarray, np.ndarray]:
    """Host-side helper (static group sizes): pad each expert's row group to
    a block_m multiple.  Returns (padded rows, expert_map, row_index) where
    row_index scatters padded rows back to originals (-1 = padding)."""
    E = len(group_sizes)
    padded_sizes = [(-(-int(g) // block_m)) * block_m for g in group_sizes]
    total = sum(padded_sizes)
    out = np.zeros((total,) + x.shape[1:], dtype=x.dtype)
    emap = []
    ridx = np.full((total,), -1, np.int64)
    src = 0
    dst = 0
    xnp = np.asarray(x)
    for e in range(E):
        g = int(group_sizes[e])
        out[dst:dst + g] = xnp[src:src + g]
        ridx[dst:dst + g] = np.arange(src, src + g)
        emap.extend([e] * (padded_sizes[e] // block_m))
        src += g
        dst += padded_sizes[e]
    return (jnp.asarray(out), np.asarray(emap, np.int32), ridx)
