#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: the compiled train step of the cell (``program.py``) with its
state made on the device from the seed, driven through its first three
steps on distinct batches of the cell's pool; their losses, the first
gradient (the optimizer's first moment) and the parameters' change are
kept for the check, as norms on the device and as arrays on the host.  The window then dispatches steps back to back,
cycling through the pool, for about ``--seconds`` seconds, and blocks
once at its end.  After it, the program's state is freed and the plain
reference (``reference.py``) follows the same three steps; ``check.py``
compares the two.

``--trace 0`` prints the end-to-end metrics; ``--trace 1`` runs the window
under the profiler and prints the per-layer metrics read from the trace,
the step's scope map and its last step's counters
(``bench/metrics/<name>.py``), with ``busy_s``, ``window_s`` and a
``breakdown``.  The last line of stdout is one JSON object; the numbers
compared, each beside its limit, are the last lines of stderr and the
last key of that object.  Exits non-zero, printing no result, where JAX's
first device is not a TPU or there are fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
# the TPU runtime's logs go under this run's temporary directory, not /tmp
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

WARM_STEPS = 3        # set-up steps: the first compiles or loads; the check
GIB = 2 ** 30
# the step's counters a traced run hands the readers (``ctx["counters"]``)
COUNTERS = ("moe_routed", "moe_kept", "fwd_fused")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory of the
    checkout (or the one ``JAX_COMPILATION_CACHE_DIR`` names), holding
    every program, however small, so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips: int):
    """JAX's devices, or exit non-zero: the benchmark measures TPUs only."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is a {devs[0].platform}"
                         " device, not a TPU; nothing is measured")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts the programs JAX lowers or compiles while it is open."""

    def __init__(self):
        import jax
        self.n = 0
        self._jax = jax

        def on(event: str, _secs: float, **_kw) -> None:
            if event.startswith("/jax/core/compile/") and "mlir" in event:
                self.n += 1
        self._on = on
        jax.monitoring.register_event_duration_secs_listener(on)

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def _same_layout(state, shardings) -> bool:
    import jax
    return all(s.is_equivalent_to(x.sharding, x.ndim) for x, s in
               zip(jax.tree.leaves(state), jax.tree.leaves(shardings)))


def set_up(cell, seed: int, devices, step_wrapper: Optional[Callable] = None):
    """Build the program's step and state, run the first steps, keep what
    the check needs.  Returns a dict the window and the check use."""
    import jax

    from bench import data
    from bench import weights as W
    from bench.program import Program
    from bench.reference import by_path, change_of, norm, on_host

    marks = [("start", time.perf_counter())]
    prog = Program(cell.config, cell.traffic, devices)
    key = W.seed_key(seed)
    state = prog.make_state(key)
    jax.block_until_ready(state)
    marks.append(("weights", time.perf_counter()))
    pool = [prog.place(t) for t in data.pool(cell.traffic, prog.dims.vocab,
                                             seed)]
    marks.append(("batches", time.perf_counter()))
    step_fn = prog.step_fn if step_wrapper is None \
        else step_wrapper(prog.step_fn, prog)
    step = jax.jit(step_fn, donate_argnums=0)
    compiled = step.lower(state, pool[0]).compile()
    # where the step returns its state in another layout than it was
    # given (ZeRO over a mesh), move the state to the layout the step
    # keeps, so that one executable runs every step
    kept = compiled.output_shardings[0]
    if not _same_layout(state, kept):
        state = jax.device_put(state, kept)
        compiled = step.lower(state, pool[0]).compile()
        if not _same_layout(state, compiled.output_shardings[0]):
            raise RuntimeError("the step's state layout does not settle")
    marks.append(("compile", time.perf_counter()))
    b1 = prog.adamw.b1
    m_norms = jax.jit(lambda m: jax.tree.map(
        lambda x: norm(x) / (1.0 - b1), m))
    a, d = prog.arch, prog.dims
    change = jax.jit(lambda k, master: change_of(a, d, k, master))
    losses, times, grad, grad_arrays = [], [], None, None
    for i in range(WARM_STEPS):
        t = time.perf_counter()
        state, metrics = compiled(state, pool[i % len(pool)])
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
        if i == 0:
            grad, grad_arrays = by_path(m_norms(state.m)), on_host(state.m)
    norms, delta = change(key, state.master)
    readings = {"loss": losses, "grad": grad, "change": by_path(norms),
                "grad_arrays": grad_arrays, "change_arrays": on_host(delta)}
    del delta
    marks.append(("steps", time.perf_counter()))
    phases = ", ".join(f"{b[0]} {b[1] - a[1]:.2f}"
                       for a, b in zip(marks, marks[1:]))
    log(f"[setup] {cell.name}: losses {losses}; step times "
        f"{[round(t, 4) for t in times]} s; backend start "
        f"{marks[0][1] - T_START:.2f} s, then {phases} s")
    return {"prog": prog, "state": state, "pool": pool,
            "compiled": compiled, "readings": readings,
            "step_s": min(times[1:]) if len(times) > 1 else times[0]}


def window(run: Dict[str, Any], seconds: float, annotate: bool = False):
    """Dispatch steps back to back for about ``seconds``; block at the end.
    Returns (steps, wall seconds, start time, host dispatch times)."""
    import jax
    n = max(1, math.ceil(seconds / run["step_s"]))
    state, pool, compiled = run["state"], run["pool"], run["compiled"]
    P = len(pool)
    dispatch = []
    if annotate:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
    t0 = time.perf_counter()
    for i in range(n):
        ts = time.perf_counter()
        if annotate:
            with StepTraceAnnotation("train", step_num=i):
                with TraceAnnotation("batch"):
                    batch = pool[(WARM_STEPS + i) % P]
                with TraceAnnotation("dispatch"):
                    state, _ = compiled(state, batch)
        else:
            state, _ = compiled(state, pool[(WARM_STEPS + i) % P])
        dispatch.append(time.perf_counter() - ts)
    if annotate:
        with TraceAnnotation("block"):
            jax.block_until_ready(state)
    else:
        jax.block_until_ready(state)
    t1 = time.perf_counter()
    run["state"] = state
    return n, t1 - t0, t0, dispatch


def traced_window(cell, run: Dict[str, Any], seconds: float, devices,
                  keep: Optional[Callable] = None):
    """The window under the profiler, as ``--trace 1`` runs it.  Returns
    the trace, the ``ctx`` the per-layer readers take and the window's
    wall seconds.  ``ctx`` holds the step's scope map (each op name of
    the compiled step to its ``op_name`` path, ``repro.scopes``) and the
    counters the window's last step returned, read once the window has
    blocked.  ``keep(dir)`` sees the profile's directory before it is
    removed."""
    import jax

    from bench import trace as T
    from repro.scopes import scope_map

    compiled = run["compiled"]
    smap = scope_map(compiled.as_text())
    last: Dict[str, Any] = {}

    def step(state, batch):
        state, m = compiled(state, batch)
        last["metrics"] = m
        return state, m

    run["compiled"] = step
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("window"):
            n, wall, _, _ = window(run, seconds, annotate=True)
        jax.profiler.stop_trace()
        tr = T.load(tdir)
        if keep is not None:
            keep(tdir)
    finally:
        run["compiled"] = compiled
        shutil.rmtree(tdir, ignore_errors=True)
    m = last["metrics"]
    ctx = {"config": cell.config, "traffic": cell.traffic,
           "chips": cell.chips, "steps": n,
           "kind": devices[0].device_kind, "scopes": smap,
           "counters": {k: int(m[k]) for k in COUNTERS if k in m}}
    return tr, ctx, wall


def hbm_bytes(compiled) -> int:
    """Bytes the step's executable needs at its peak on the fullest
    device: arguments + outputs - aliased + temporaries."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def device_info(devices, chips: int) -> Dict[str, Any]:
    used = list(devices)[:chips]
    peaks = []
    for dv in used:
        st = dv.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    d0 = used[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def memory_line(run, devices, chips: int) -> None:
    prog = run["prog"]
    stats = [dv.memory_stats() or {} for dv in list(devices)[:chips]]
    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_reserved",
            "bytes_limit")
    est = prog.estimate()
    log("[memory] memory_stats per device: " + json.dumps(
        [{k: s.get(k) for k in keys} for s in stats]))
    log(f"[memory] estimate_memory total {est.total / GIB:.4f} GiB: "
        + ", ".join(f"{k}={v / GIB:.4f}" for k, v in est.breakdown().items()
                    if k != "total"))


def check(cell, seed: int, readings: Dict[str, Any]):
    from bench import check as C
    from bench import reference
    t = time.perf_counter()
    ref = reference.readings(cell.config, cell.traffic, seed)
    log(f"[check] reference took {time.perf_counter() - t:.1f} s; "
        f"losses program {readings['loss']} reference {ref['loss']}")
    return C.judge(C.gaps(readings, ref), cell.limits)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             step_wrapper: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object (not yet printed)."""
    import gc

    import jax

    counter = CompileCounter()
    run = set_up(cell, seed, devices, step_wrapper)
    metrics: Dict[str, Dict[str, Any]] = {}
    device = None
    breakdown = None
    if not trace:
        compiles_before = counter.n
        n, wall, t0, dispatch = window(run, seconds)
        in_window = counter.n - compiles_before
        setup_s = t0 - T_START
        tokens = n * run["prog"].tokens_per_step
        log(f"[window] {n} steps in {wall:.4f} s; {in_window} compilations "
            f"inside; host dispatch per step median "
            f"{statistics.median(dispatch) * 1e3:.3f} ms max "
            f"{max(dispatch) * 1e3:.3f} ms; set-up step time "
            f"{run['step_s']:.4f} s")
        values = {"tokens_per_s": tokens / wall,
                  "hbm_peak_gib": hbm_bytes(run["compiled"]) / GIB,
                  "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": values[k], "unit": units[k]}
                   for k in units}
    else:
        from bench.cells import load_metric
        tr, ctx, _ = traced_window(cell, run, seconds, devices)
        n = ctx["steps"]
        for m in cell.per_layer:
            v = load_metric(m["name"])(tr, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = {"busy_s": tr.busy_s(), "window_s": tr.window_s()}
        breakdown = tr.breakdown()
        log(f"[trace] {n} steps; busy {device['busy_s']:.4f} s of "
            f"{device['window_s']:.4f} s; last step's counters "
            f"{json.dumps(ctx['counters'])}; per-layer {json.dumps(metrics)}")
    dev = device_info(devices, cell.chips)
    if device:
        dev.update(device)
    memory_line(run, devices, cell.chips)
    counter.close()
    readings = run["readings"]
    del run
    gc.collect()
    correct, numbers = check(cell, seed, readings)
    # the attempts are the steps; the checked ones are the set-up steps
    out = {"correct": correct, "attempted": WARM_STEPS + n,
           "failed": 0 if correct else WARM_STEPS,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = numbers
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench.cells import resolve
    cell = resolve(args.workload)
    enable_cache()
    devices = tpu_devices(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, v in out["check"].items():
        log(f"{name} {v['value']:.6e} limit {v['limit']:.6e}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
