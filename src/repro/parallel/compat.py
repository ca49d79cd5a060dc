"""The repo's one mesh constructor and its ``shard_map`` spelling.

Every mesh in the repository — production, debug, launcher, benchmark and
test meshes — comes from :func:`make_mesh`.  ``jax.make_mesh`` defaults to
``Explicit`` axis types, under which ``with_sharding_constraint`` refuses
the ZeRO residency constraints and ``lax.scan`` refuses data-sharded
inputs; the executor and the GSPMD step are written for ``Auto`` axes
(the partitioner chooses every layout no constraint pins), so that is what
this constructor builds.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """An all-``Auto`` mesh of ``shape`` over ``devices`` (default: every
    device of the default backend)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f: Callable, *, mesh, in_specs, out_specs) -> Callable:
    """``jax.shard_map`` with the replication check disabled.

    The executor and MoE all-to-all paths return values whose replication
    across unrelated axes is established by explicit psums, which the static
    checker cannot always verify.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
