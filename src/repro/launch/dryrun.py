import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) on placeholder devices; record memory_analysis / cost_analysis /
collective bytes for EXPERIMENTS.md §Dry-run and the §Roofline table.

The two lines above MUST precede any other import (jax locks the device
count at first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-v3 --shape train_4k \
      --zero os+g --recompute full --attn chunked --n-micro 16
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1_5b --shape train_4k \
      --pp 4 --n-micro 8 --schedule dualpipe
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1_5b --shape train_4k \
      --pp 2 --tp 2 --zero os --n-micro 8

Arguments (see ``main()``): ``--arch``/``--shape`` or ``--all`` select the
combos; ``--zero``, ``--recompute``, ``--attn``, ``--n-micro``,
``--capacity-factor``, ``--moe-impl`` configure the lowered program;
``--mesh-shape``/``--multi-pod`` the fake device grid, ``--tp N`` overrides
just its 'model' axis (so ``--pp --tp --zero`` compose into joint 3D+ZeRO
probes on small fake meshes); ``--sp N`` (N = the TP degree) additionally
shards the probe's boundary/residual sequence dims over 'model' and sets
the analytic sp divisor — the measurement side of the executor's Megatron
sequence parallelism; ``--ep N`` (MoE archs, N = 1 or the TP degree) pins
the expert placement on both sides — N>1 shards expert weights on their
expert dim over 'model' (the executor's EP layout) and sets the analytic
ep divisor, N=1 pins the ETP layout — so an ``__ep1``/``__ep2`` artifact
pair measures the (E/ep, C, h) dispatch-buffer shrink.  With ``--pp N``
(> 1) each pipeline rank is
compiled as its own program holding the schedule's in-flight microbatch
counts (``--schedule {1f1b,interleaved,dualpipe,zb1p}``, ``--pp-chunks`` virtual
stages per rank) next to ``estimate_memory(stage=r, schedule=...)`` — the
measurement side of ``docs/pipeline-schedules.md``.

Artifacts: one JSON per combo in ``benchmarks/artifacts/dryrun/<tag>.json``
(tag =
arch__shape__mesh[__ppN[__<schedule><v>]][__z<zero>][__sp<N>][__ep<N>][suffix];
the mesh component encodes tp, the ``__z`` component appears for
non-default ``--zero``, ``__sp`` for ``--sp`` > 1, ``__ep`` whenever
``--ep`` is explicit) with status,
lower/compile wall-times, ``memory_analysis`` fields, flops/bytes from
``cost_analysis``, per-collective HLO byte counts (plain runs) or the
per-rank records (``--pp`` runs: layers, per-chunk in-flight, memory,
analytic breakdown, plus top-level ``tp``/``zero``/``sp``).
Existing artifacts are reused unless ``--force``;
``benchmarks/validate_memory.py`` consumes them.
"""

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_spec
from repro.core.parallel_config import RecomputePolicy, ZeROStage
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (SHAPES, batch_shardings, batch_specs,
                                cache_shardings, input_specs,
                                shape_skip_reason, spec_for_shape)
from repro.models import build_model
from repro.models.transformer import ModelOptions
from repro.optim.adamw import TrainState
from repro.parallel.axes import axis_rules
from repro.parallel.sharding import grad_shardings, state_shardings
from repro.serving.decode import make_serve_step
from repro.train.loop import TrainConfig, make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

_OP_DEF_RE = re.compile(
    r"=\s*(.*?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-permute)(-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        nbytes = _DTYPE_BYTES.get(dt)
        if nbytes is None:
            continue   # token like u32 index types unknown -> skipped above
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * nbytes
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum result sizes of every collective op-def in optimized HLO
    (handles variadic tuple-shaped collectives; skips -done halves so async
    pairs count once)."""
    per_kind: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_DEF_RE.search(line)
        if not m:
            continue
        shape_text, kind, suffix = m.groups()
        if suffix == "-done":
            continue
        nbytes = _shape_bytes(shape_text)
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes_by_kind": per_kind, "counts": counts,
            "total_bytes": sum(per_kind.values())}


def _mem_dict(mem) -> Dict[str, float]:
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    out = {}
    for k in keys:
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def build_step(arch: str, shape_name: str, *, attn_impl: str = "naive",
               recompute: str = "none", zero: str = "os+g",
               n_micro: int = 1, capacity_factor: float = 1.25,
               scan_layers: bool = True, spec_override=None,
               moe_impl: str = "scatter", backend: str = "reference"):
    """Returns (fn, abstract_args, in_shardings, out_shardings, meta)."""
    spec0 = spec_override if spec_override is not None else get_spec(arch)
    spec = spec_for_shape(spec0, shape_name)
    info = SHAPES[shape_name]
    opts = ModelOptions(attn_impl=attn_impl,
                        recompute=RecomputePolicy(recompute),
                        capacity_factor=capacity_factor,
                        scan_layers=scan_layers,
                        moe_impl=moe_impl,
                        backend=backend)
    model = build_model(spec, opts)
    mesh = None  # bound by caller via axis_rules
    z = ZeROStage(zero)

    if info["kind"] == "train":
        from repro.optim.adamw import init_train_state
        step = make_train_step(model, TrainConfig(n_micro=n_micro))
        abstract_state = jax.eval_shape(init_train_state,
                                        model.abstract_params())
        batch = batch_specs(spec, info["batch"], info["seq"])
        return dict(kind="train", fn=step, model=model, spec=spec,
                    abstract_state=abstract_state, batch=batch, zero=z)
    if info["kind"] == "prefill":
        def prefill_fn(params, batch):
            logits, _ = model.forward(params, batch)
            return logits
        return dict(kind="prefill", fn=prefill_fn, model=model, spec=spec,
                    abstract_params=model.abstract_params(),
                    batch=batch_specs(spec, info["batch"], info["seq"]),
                    zero=z)
    # decode
    serve = make_serve_step(model)
    ins = input_specs(spec0, shape_name, model=model)
    return dict(kind="decode", fn=serve, model=model, spec=spec,
                abstract_params=model.abstract_params(),
                cache=ins["cache"], tokens=ins["tokens"], zero=z)


def lower_and_compile(built: Dict[str, Any], mesh) -> Dict[str, Any]:
    kind = built["kind"]
    z = built["zero"]
    t0 = time.perf_counter()
    with axis_rules(mesh):
        if kind == "train":
            st_sh = state_shardings(built["abstract_state"], mesh, z)
            b_sh = batch_shardings(built["batch"], mesh)
            lowered = jax.jit(
                built["fn"],
                in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None),
            ).lower(built["abstract_state"], built["batch"])
        elif kind == "prefill":
            p_sh = state_shardings(
                _fake_state(built["abstract_params"]), mesh, z).params
            b_sh = batch_shardings(built["batch"], mesh)
            lowered = jax.jit(
                built["fn"], in_shardings=(p_sh, b_sh),
            ).lower(built["abstract_params"], built["batch"])
        else:
            p_sh = state_shardings(
                _fake_state(built["abstract_params"]), mesh, z).params
            c_sh = cache_shardings(built["cache"], mesh)
            t_sh = batch_shardings({"t": built["tokens"]}, mesh)["t"]
            lowered = jax.jit(
                built["fn"], in_shardings=(p_sh, c_sh, t_sh),
                out_shardings=(None, c_sh),
            ).lower(built["abstract_params"], built["cache"], built["tokens"])
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
    return dict(lowered=lowered, compiled=compiled,
                t_lower=t_lower, t_compile=t_compile)


def _fake_state(abstract_params):
    from repro.optim.adamw import TrainState
    z = jax.ShapeDtypeStruct((), jnp.int32)
    return TrainState(step=z, params=abstract_params,
                      master=abstract_params, m=abstract_params,
                      v=abstract_params)


def _stage_input_shardings(mesh, arrs, sp: int = 1):
    """Shardings for the per-rank probe's in-flight boundary arrays
    (k, b, s[, h]): batch over the data axes; with ``sp`` > 1 additionally
    the seq dim of the bf16 boundary activations over 'model' — the
    executor's seq-sharded residency, so the probe's measured bytes carry
    the /sp divisor the analytic column models."""
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    out = []
    for a in arrs:
        entries = [None] * len(a.shape)
        if ba and a.shape[1] % int(np.prod([mesh.shape[x] for x in ba])) == 0:
            entries[1] = ba
        if sp > 1 and len(a.shape) >= 4 and "model" in mesh.axis_names \
                and a.shape[2] % mesh.shape["model"] == 0:
            entries[2] = "model"
        out.append(NamedSharding(mesh, P(*entries)))
    return tuple(out)


def _rank_params_slice(params, spec, chunks, firsts, lasts):
    """Heterogeneous per-rank parameter tree for a multi-chunk rank:
    {'shared': embed/final_norm/head owned by any of the rank's chunks,
    'chunks': one layers-only slice per chunk}.  Shared pieces are hoisted
    so a rank whose chunks own both ends (dualpipe rank 0) holds one copy —
    matching the stacked runtime layout and the analytic ``device_params``.
    """
    from repro.models.pipeline import chunk_params_slice
    shared = {}
    if any(firsts) or (spec.tie_embeddings and any(lasts)):
        shared["embed"] = params["embed"]
    if any(lasts):
        shared["final_norm"] = params["final_norm"]
        if not spec.tie_embeddings and "head" in params:
            shared["head"] = params["head"]
    # a list, not a tuple: adamw_update unpacks its per-leaf update triples
    # with is_leaf=isinstance(x, tuple)
    return {"shared": shared,
            "chunks": [chunk_params_slice(params, spec, ls, with_embed=False,
                                          with_head=False) for ls in chunks]}


def _make_rank_probe(spec, opts, chunks, firsts, lasts, in_flight):
    """Per-rank training-memory probe: for each of the rank's layer chunks,
    forward ``in_flight[c]`` microbatches with live activations (a scan
    whose backward consumes them last-in), then one accumulated backward +
    AdamW update — the schedule residency of the rank at its byte-weighted
    peak tick as one compilable program.  The last model chunk reduces via
    the real CE; all others via a mean-square surrogate (same backward
    structure)."""
    from repro.models.pipeline import make_chunk_fn
    from repro.optim.adamw import AdamWConfig, adamw_update
    fns = [make_chunk_fn(spec, opts, ls, is_first=f, is_last=l)
           for ls, f, l in zip(chunks, firsts, lasts)]
    total_k = max(sum(in_flight), 1)

    def probe(state, *arrs_flat):
        arrs_per_chunk, i = [], 0
        for c in range(len(chunks)):
            # first chunk: tokens only; interior: boundary x only;
            # last (and not first): boundary x + tokens for the CE
            n = 2 if (lasts[c] and not firsts[c]) else 1
            if in_flight[c] == 0:
                arrs_per_chunk.append(None)
                continue
            arrs_per_chunk.append(arrs_flat[i:i + n])
            i += n

        def scalar(params_):
            tot = jnp.zeros((), jnp.float32)
            for c, fn in enumerate(fns):
                if arrs_per_chunk[c] is None:
                    continue
                cp = dict(params_["chunks"][c])
                sh = params_["shared"]
                if firsts[c] or (spec.tie_embeddings and lasts[c]):
                    cp["embed"] = sh["embed"]
                if lasts[c]:
                    cp["final_norm"] = sh["final_norm"]
                    if "head" in sh:
                        cp["head"] = sh["head"]
                is_first, is_last = firsts[c], lasts[c]

                def body(acc, inp, fn=fn, is_first=is_first, is_last=is_last,
                         cp=cp):
                    if is_first:
                        x, tk = None, inp[0]
                    elif is_last:
                        x, tk = inp
                    else:
                        (x,), tk = inp, None
                    out, aux = fn(cp, x, tk)
                    if is_last:
                        targets = tk[:, 1:]
                        lg = out[:, :-1].astype(jnp.float32)
                        logz = jax.scipy.special.logsumexp(lg, axis=-1)
                        gold = jnp.take_along_axis(
                            lg, targets[..., None], axis=-1)[..., 0]
                        val = jnp.mean(logz - gold)
                    else:
                        val = jnp.mean(jnp.square(out.astype(jnp.float32)))
                    return acc + val + 0.01 * aux, None

                part, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                       arrs_per_chunk[c])
                tot = tot + part
            return tot / total_k
        grads = jax.tree.map(lambda g: g.astype(jnp.float32),
                             jax.grad(scalar)(state.params))
        new_state, _ = adamw_update(state, grads, AdamWConfig())
        return new_state

    return probe


def run_pp(arch: str, shape_name: str, pp: int, *, multi_pod: bool = False,
           force: bool = False, tag_suffix: str = "", mesh_shape=None,
           schedule: str = "1f1b", n_chunks: int = 1, sp: int = 1,
           ep: Optional[int] = None,
           **build_kw) -> Dict[str, Any]:
    """--pp N [--schedule ...]: lower + compile each pipeline rank as its
    own program on the rank's (data/pp, model) sub-mesh and record per-rank
    memory_analysis next to the analytical estimate_memory(stage=r,
    schedule=...).

    Each rank's probe holds the schedule's in-flight microbatch counts at
    the rank's byte-weighted peak tick — per chunk under interleaved /
    dualpipe — so the measured temp bytes carry the same schedule residency
    the analytic column models.  This is the heterogeneous view (true rank
    params: embedding with the first model chunk, head with the last, both
    ends on the boundary ranks under dualpipe) — no SPMD padding — so the
    records are directly comparable to the paper's per-stage Tables 4/5
    arithmetic."""
    from repro.core import estimate_memory, make_schedule
    from repro.core.activations import (layers_activation_bytes,
                                        rank_chunk_layers)
    from repro.core.parallel_config import ParallelConfig
    from repro.core.schedules import norm_chunks, n_model_chunks
    from repro.models.pipeline import check_pipeline_supported
    from repro.optim.adamw import init_train_state

    os.makedirs(ART_DIR, exist_ok=True)
    data, model_ax = tuple(mesh_shape) if mesh_shape else (16, 16)
    mesh_tag = ("pod2x" if multi_pod else "pod") + f"{data}x{model_ax}"
    v = norm_chunks(schedule, n_chunks)
    sched_tag = "" if schedule == "1f1b" else f"__{schedule}{v}"
    zero = build_kw.get("zero", "os+g")
    zero_tag = "" if zero == "os+g" else f"__z{zero.replace('+', '')}"
    if sp not in (1, model_ax):
        raise ValueError(f"--sp must be 1 or the TP degree {model_ax} "
                         f"(Megatron SP ties sp to tp), got {sp}")
    sp_tag = "" if sp == 1 else f"__sp{sp}"
    # --ep: explicit EP degree.  None keeps the legacy behaviour (analytic
    # ep = min(tp, n_routed), measured layout = the DEFAULT_RULES expert
    # shard) under the legacy untagged artifact name; an explicit value
    # pins BOTH sides — ep>1 shards the expert dim over 'model' (full axis,
    # like the executor's a2a layout), ep=1 pins the ETP layout (expert-ff
    # over 'model', experts replicated) — so an __ep1/__ep2 artifact pair
    # isolates exactly the dispatch-buffer /ep shrink.
    ep_tag = "" if ep is None else f"__ep{ep}"
    # --backend pallas: the kernel fast path.  The probe only COMPILES
    # (interpret-mode pallas lowers to pure jax ops off-TPU), but the
    # analytic column switches to flash accounting — cfg.attn_impl drops
    # the resident 5·b·n_h·s² buffers — so the tagged __pallas artifact
    # pairs with its untagged twin to isolate exactly that delta.
    backend = build_kw.get("backend", "reference")
    bk_tag = "" if backend == "reference" else "__pallas"
    tag = (f"{arch}__{shape_name}__{mesh_tag}__pp{pp}{sched_tag}{zero_tag}"
           f"{sp_tag}{ep_tag}{bk_tag}{tag_suffix}")
    path = os.path.join(ART_DIR, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    info = SHAPES[shape_name]
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "pp": pp,
                           "schedule": schedule, "n_chunks": v,
                           "tp": model_ax, "zero": zero, "sp": sp,
                           "backend": backend,
                           "mesh": mesh_tag, "options": build_kw}
    if ep is not None:
        rec["ep"] = ep
    try:
        if info["kind"] != "train":
            raise NotImplementedError("--pp covers training shapes only "
                                      "(the paper's per-stage analysis)")
        spec = spec_for_shape(get_spec(arch), shape_name)
        check_pipeline_supported(spec)
        if data % pp:
            raise ValueError(f"pp={pp} must divide data axis {data}")
        n_micro = max(build_kw.get("n_micro", 1), 1)
        opts = ModelOptions(
            attn_impl=build_kw.get("attn_impl", "naive"),
            recompute=RecomputePolicy(build_kw.get("recompute", "none")),
            capacity_factor=build_kw.get("capacity_factor", 1.25),
            moe_impl=build_kw.get("moe_impl", "scatter"),
            backend=backend)
        model = build_model(spec, opts)
        params_abs = model.abstract_params()
        mesh = make_production_mesh(multi_pod=multi_pod,
                                    shape=(data // pp, model_ax))
        dp = (data // pp) * (2 if multi_pod else 1)
        b_micro = max(info["batch"] // n_micro, 1)
        n_exp = spec.moe.n_routed if spec.is_moe else None
        if ep is None:                  # legacy: analytic ep follows the mesh
            ep_eff = min(model_ax, n_exp) if n_exp else 1
        else:
            if not spec.is_moe:
                raise ValueError(f"--ep needs an MoE arch, {arch} is dense")
            if ep not in (1, model_ax):
                raise ValueError(
                    f"--ep must be 1 or the TP degree {model_ax} (the "
                    f"expert-dim shard spans the whole 'model' axis, like "
                    f"the executor's a2a group), got {ep}")
            if n_exp % ep:
                raise ValueError(f"--ep {ep} does not divide "
                                 f"n_routed={n_exp}")
            ep_eff = ep
        rec["ep"] = ep_eff
        cfg = ParallelConfig(
            dp=dp, tp=model_ax, pp=pp, ep=ep_eff, etp=1, sp=sp > 1,
            zero=ZeROStage(build_kw.get("zero", "os+g")),
            recompute=RecomputePolicy(build_kw.get("recompute", "none")),
            micro_batch=max(b_micro // dp, 1), seq_len=info["seq"],
            attn_impl="flash" if backend == "pallas"
            else build_kw.get("attn_impl", "naive"))
        sched = make_schedule(schedule, pp, n_micro, n_chunks=v)
        all_chunks = rank_chunk_layers(spec, pp, schedule=schedule,
                                       n_chunks=v)
        g_total = n_model_chunks(schedule, pp, v)
        stages = []
        # --sp: route the logical "seq" axis onto 'model' so the probe's
        # boundary/residual constraints shard the sequence — the measured
        # counterpart of the analytic /sp divisor.  --ep: pin the expert
        # rules to the probed placement (ep>1: expert dim over 'model',
        # full ff — the executor's EP layout; ep=1: the ETP layout) so the
        # __ep pair's measured dispatch-buffer bytes track the analytic
        # (E/ep, C, h) term.
        probe_rules: Dict[str, Any] = {}
        if sp > 1:
            probe_rules["seq"] = "model"
        if ep is not None:
            probe_rules.update({"expert": "model", "expert_ff": None}
                               if ep > 1 else
                               {"expert": None, "expert_ff": "model"})
        with axis_rules(mesh, probe_rules or None):
            for r in range(pp):
                chunks = all_chunks[r]
                placed = sched.placement[r]
                firsts = [g == 0 for g in placed]
                lasts = [g == g_total - 1 for g in placed]
                weights = [layers_activation_bytes(spec, cfg, ls)
                           for ls in chunks]
                _, ks = sched.peak_profile(r, weights)
                abstract_rank = jax.eval_shape(
                    lambda p: _rank_params_slice(p, spec, chunks, firsts,
                                                 lasts), params_abs)
                abstract_state = jax.eval_shape(init_train_state,
                                                abstract_rank)
                arrs = []
                for c, k in enumerate(ks):
                    if k == 0:
                        continue
                    if firsts[c]:
                        arrs.append(jax.ShapeDtypeStruct(
                            (k, b_micro, info["seq"]), jnp.int32))
                    else:
                        arrs.append(jax.ShapeDtypeStruct(
                            (k, b_micro, info["seq"], spec.h), jnp.bfloat16))
                        if lasts[c]:
                            arrs.append(jax.ShapeDtypeStruct(
                                (k, b_micro, info["seq"]), jnp.int32))
                probe = _make_rank_probe(spec, opts, chunks, firsts, lasts,
                                         list(ks))
                st_sh = state_shardings(abstract_state, mesh, cfg.zero,
                                        rules=probe_rules or None)
                in_sh = _stage_input_shardings(mesh, arrs, sp=sp)
                t0 = time.perf_counter()
                compiled = jax.jit(
                    probe, in_shardings=(st_sh,) + in_sh,
                    out_shardings=st_sh,
                ).lower(abstract_state, *arrs).compile()
                t_c = time.perf_counter() - t0
                mem = compiled.memory_analysis()
                est = estimate_memory(spec, cfg, stage=r, schedule=schedule,
                                      n_chunks=v, n_micro=n_micro)
                stages.append({
                    "stage": r,
                    "layers": [int(l) for ls in chunks for l in ls],
                    "chunks": [{"model_chunk": int(placed[c]),
                                "layers": [int(l) for l in chunks[c]],
                                "in_flight": int(ks[c])}
                               for c in range(len(chunks))],
                    "in_flight": int(sum(ks)), "t_compile_s": t_c,
                    "memory": _mem_dict(mem),
                    "analytic": {kk: int(vv)
                                 for kk, vv in est.breakdown().items()},
                })
                print(f"[{tag}] rank {r}: in_flight={list(ks)} "
                      f"temp={stages[-1]['memory'].get('temp_size_in_bytes', 0)/2**30:.2f} GiB "
                      f"analytic_act={est.activations/2**30:.2f} GiB")
        temps = [st["memory"].get("temp_size_in_bytes", 0) for st in stages]
        acts = [st["analytic"]["activations"] for st in stages]
        rec.update(status="ok", stages=stages,
                   measured_temp_stage0_over_last=(temps[0] / max(temps[-1], 1)),
                   analytic_act_stage0_over_last=(acts[0] / max(acts[-1], 1)))
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[{tag}] {rec['status']}"
          + (f" ({rec.get('error', '')})" if rec["status"] == "error" else ""))
    return rec


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            force: bool = False, tag_suffix: str = "",
            mesh_shape=None, **build_kw) -> Dict[str, Any]:
    os.makedirs(ART_DIR, exist_ok=True)
    if mesh_shape is not None:
        mesh_tag = "pod" + ("2x" if multi_pod else "") \
            + "x".join(map(str, mesh_shape))
    else:
        mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    bk_tag = "" if build_kw.get("backend", "reference") == "reference" \
        else "__pallas"
    tag = f"{arch}__{shape_name}__{mesh_tag}{bk_tag}{tag_suffix}"
    path = os.path.join(ART_DIR, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    spec = get_spec(arch)
    skip = shape_skip_reason(spec, shape_name)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_tag, "options": build_kw}
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
    else:
        try:
            mesh = make_production_mesh(multi_pod=multi_pod,
                                        shape=mesh_shape)
            built = build_step(arch, shape_name, **build_kw)
            art = lower_and_compile(built, mesh)
            compiled = art["compiled"]
            mem = compiled.memory_analysis()
            print(mem)                       # proves it fits / reports bytes
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):   # older jax: list of dicts
                cost = cost[0] if cost else {}
            print({k: v for k, v in list(cost.items())[:8]})
            hlo = compiled.as_text()
            rec.update(
                status="ok",
                t_lower_s=art["t_lower"],
                t_compile_s=art["t_compile"],
                memory=_mem_dict(mem),
                flops=float(cost.get("flops", -1)),
                bytes_accessed=float(cost.get("bytes accessed", -1)),
                transcendentals=float(cost.get("transcendentals", -1)),
                collectives=collective_bytes(hlo),
                hlo_size_chars=len(hlo),
            )
        except Exception as e:
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-4000:]
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    print(f"[{tag}] {status}" + (f" ({rec.get('error','')})"
                                 if status == "error" else ""))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero", default="os+g",
                    choices=[z.value for z in ZeROStage])
    ap.add_argument("--recompute", default="none",
                    choices=[r.value for r in RecomputePolicy])
    ap.add_argument("--attn", default="naive", choices=["naive", "chunked"])
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"],
                    help="kernel backend for the hot ops: 'pallas' routes "
                         "rmsnorm/attention/grouped-mlp through the Pallas "
                         "kernels (interpret mode off-TPU; compile-only in "
                         "this probe), upgrades causal attention to the "
                         "flash kernel and switches the analytic column to "
                         "flash accounting (drops the resident 5·b·n_h·s² "
                         "buffers); tags the artifact __pallas — run the "
                         "tagged/untagged pair to measure the delta")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages: >1 compiles each stage as its own "
                         "program and records per-stage memory_analysis")
    ap.add_argument("--tp", type=int, default=None,
                    help="override the mesh's 'model' axis (TP degree) — "
                         "with --pp/--zero this gives joint 3D+ZeRO probes "
                         "on small fake meshes, e.g. --pp 2 --tp 2 --zero os")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel degree for --pp probes (1 or "
                         "the TP degree — Megatron SP ties sp to tp): "
                         "shards the probe's boundary/residual seq dims "
                         "over 'model', tags the artifact __sp<N> and sets "
                         "the analytic sp divisor")
    ap.add_argument("--ep", type=int, default=None,
                    help="expert-parallel degree for --pp probes on MoE "
                         "archs (1 or the TP degree — the expert shard "
                         "spans the whole 'model' axis, like the "
                         "executor's a2a group): >1 shards expert weights "
                         "on their expert dim (full ff), 1 pins the ETP "
                         "layout; tags the artifact __ep<N> and sets the "
                         "analytic ep divisor — run the __ep1/__ep2 pair "
                         "to measure the (E/ep, C, h) dispatch-buffer "
                         "shrink")
    ap.add_argument("--schedule", default="1f1b",
                    choices=["1f1b", "interleaved", "dualpipe", "zb1p"],
                    help="pipeline schedule for --pp probes: sets per-rank "
                         "chunk layout and in-flight residency (zb1p: 1f1b "
                         "activation residency + the fp32 pending-dW stash "
                         "in the analytic grads column)")
    ap.add_argument("--pp-chunks", type=int, default=None,
                    help="virtual stages per rank (interleaved: >=2; "
                         "defaults to 2 for interleaved/dualpipe)")
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--moe-impl", default="scatter",
                    choices=["scatter", "a2a"])
    ap.add_argument("--mesh-shape", default=None,
                    help="override per-pod grid, e.g. 32x8")
    ap.add_argument("--tag-suffix", default="")
    args = ap.parse_args()
    mesh_shape = tuple(int(x) for x in args.mesh_shape.split("x")) \
        if args.mesh_shape else None
    if args.tp:
        base = mesh_shape if mesh_shape else (16, 16)
        mesh_shape = (base[0], args.tp)

    build_kw = dict(zero=args.zero, recompute=args.recompute,
                    attn_impl=args.attn, n_micro=args.n_micro,
                    capacity_factor=args.capacity_factor,
                    moe_impl=args.moe_impl, backend=args.backend)

    combos = []
    if args.all:
        for a in ASSIGNED:
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch & --shape or --all"
        combos = [(args.arch, args.shape)]

    if args.sp > 1 and args.pp <= 1:
        ap.error("--sp applies to the per-rank --pp probes; pass --pp N "
                 "(the plain-probe path would silently measure replicated "
                 "sequence dims under an __sp tagless artifact)")
    if args.ep is not None and args.pp <= 1:
        ap.error("--ep applies to the per-rank --pp probes; pass --pp N")
    failures = 0
    n_chunks = args.pp_chunks if args.pp_chunks is not None \
        else (1 if args.schedule in ("1f1b", "zb1p") else 2)
    for a, s in combos:
        if args.pp > 1:
            rec = run_pp(a, s, args.pp, multi_pod=args.multi_pod,
                         force=args.force, tag_suffix=args.tag_suffix,
                         mesh_shape=mesh_shape, schedule=args.schedule,
                         n_chunks=n_chunks, sp=args.sp, ep=args.ep,
                         **build_kw)
        else:
            rec = run_one(a, s, multi_pod=args.multi_pod, force=args.force,
                          tag_suffix=args.tag_suffix, mesh_shape=mesh_shape,
                          **build_kw)
        if rec["status"] == "error":
            failures += 1
    print(f"done: {len(combos)} combos, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
