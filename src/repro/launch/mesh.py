"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model") — the roofline
table's mesh.  Multi-pod: (2, 16, 16) = 512 chips, axes ("pod", "data",
"model"); the "pod" axis extends data parallelism across the ICI/DCN
boundary (DP-major placement, matching the paper's DP×EDP grouping where
ZeRO shards span data×pod).

Defined as FUNCTIONS so importing this module never initialises jax device
state (the dry-run must set XLA_FLAGS before first jax use).
"""

from __future__ import annotations

from repro.core.parallel_config import ParallelConfig, ZeROStage
from repro.parallel.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False, shape=None, pp: int = 1):
    """Default single-pod (16,16) / multi-pod (2,16,16).  ``shape`` overrides
    the per-pod grid, e.g. (32, 8) — a decode-shaped mesh whose model axis
    divides small KV-head counts (§Perf hillclimb 3); total chips must stay
    256/pod.

    ``pp`` > 1 carves a leading ``pipe`` axis out of the data axis (the
    paper's world = DP·TP·PP tiling: PP groups are data-major so ZeRO's
    DP/EDP sync stays within a stage): (16,16) with pp=4 becomes the
    (4, 4, 16) mesh ('pipe', 'data', 'model')."""
    data, model = tuple(shape) if shape is not None else (16, 16)
    if pp > 1:
        if data % pp:
            raise ValueError(f"pp={pp} must divide the data axis ({data})")
        grid, axes = (pp, data // pp, model), ("pipe", "data", "model")
    else:
        grid, axes = (data, model), ("data", "model")
    if multi_pod:
        grid, axes = (2,) + grid, ("pod",) + axes
    return make_mesh(grid, axes)


def make_debug_mesh(model: int = 1, data: int = 1, pipe: int = 1):
    """Tiny mesh over however many (possibly fake) local devices exist."""
    if pipe > 1:
        return make_mesh((pipe, data, model), ("pipe", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def parallel_config_for_mesh(mesh, *, spec=None, zero: ZeROStage = ZeROStage.OS_G,
                             micro_batch: int = 1, seq_len: int = 4096,
                             recompute="none") -> ParallelConfig:
    """Analytical-model view of a mesh: TP/EP live on the 'model' axis, DP on
    data(+pod).  Used to compare estimate_memory() with XLA's
    memory_analysis() for the same configuration."""
    from repro.core.parallel_config import RecomputePolicy
    model_ax = mesh.shape.get("model", 1)
    data_ax = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    pp = mesh.shape.get("pipe", 1)
    n_exp = spec.moe.n_routed if (spec is not None and spec.is_moe) else None
    ep = min(model_ax, n_exp) if n_exp else 1
    rc = RecomputePolicy(recompute) if isinstance(recompute, str) else recompute
    return ParallelConfig(dp=data_ax, tp=model_ax, pp=pp, ep=ep, etp=1,
                          sp=True, zero=zero, recompute=rc,
                          micro_batch=micro_batch, seq_len=seq_len)
