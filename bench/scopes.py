#!/usr/bin/env python3
"""Device time per layer scope and phase of the executor's step.

The executor names its layers and ticks with ``jax.named_scope``
(``repro.scopes``).  The compiled step's HLO text maps each instruction
name, which is the op name a device trace shows (``fusion.625``,
``_gmm_jit.71``), to its ``op_name`` path (``repro.scopes.scope_map``);
this module reduces a traced window (``bench.trace.Trace``) with that
map:

* ``scope_ns``: the union of a device's leaf-op intervals whose path a
  predicate accepts; ``share``: that over the window, in %, on the chip
  where it is largest;
* ``table``: seconds per (layer, phase) and the share of busy time the
  scopes hold;
* ``host_events`` / ``named_gaps``: the longest idle gaps of device 0,
  each named by the innermost profiler host event open when it began
  (a harness span or a runtime event such as ``Wait for donation
  holds``) and by the layer and phase of the device op before it.

The metrics that read scopes (``bench/metrics/step.replay_pct.py`` and
the others) take the map from ``ctx["scopes"]`` and the step's counters
(``moe_routed``, ``moe_kept``, ``fwd_fused``) from ``ctx["counters"]``,
and read nothing where ``ctx`` holds neither; ``bench/run.py --trace 1``
hands them both (``run.traced_window``).  Run as a script, it runs one
cell's traced window the same way and prints the metrics of ``METRICS``
beside the cell's own, with the table and the named gaps::

  python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>
      [--record <dir>]

``--record`` also writes the trace (``<cell>.scoped.xplane.pb.gz``) and
the map of the ops it holds (``<cell>.scoped.scopes.json.gz``).  Exits
non-zero where JAX's first device is not a TPU.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import pathlib
import shutil
import sys
from typing import Callable, Dict, List, Optional, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace as T  # noqa: E402
from repro import scopes as S  # noqa: E402

# the metrics this module's map and counters feed
METRICS = ("step.replay_pct", "attention.device_pct", "head.device_pct",
           "optimizer.device_pct", "moe.route_pct", "moe.experts_pct",
           "grad_sync.exposed_pct", "moe.dropped_pct")
UNSCOPED = "unscoped"
NO_PHASE = "-"

Pred = Callable[[str], bool]


def scope_ns(trace: T.Trace, smap: Dict[str, str], dev: int,
             pred: Pred) -> int:
    """The union of ``dev``'s leaf-op intervals whose path ``pred``
    accepts (a name the map lacks has the path "")."""
    return T._length((a, b) for n, a, b in trace.devices[dev]
                     if pred(smap.get(n, "")))


def share(trace: T.Trace, ctx, pred: Pred) -> Optional[float]:
    """``scope_ns`` over the window, in %, on the chip where it is
    largest; None without a map or where no op's path ``pred`` accepts."""
    smap = ctx.get("scopes")
    if not smap or not trace.devices:
        return None
    if not any(pred(smap.get(n, "")) for ev in trace.devices.values()
               for n, _, _ in ev):
        return None
    win = trace.hi - trace.lo
    return 100.0 * max(scope_ns(trace, smap, d, pred) / win
                       for d in trace.devices)


def in_layers(*layers: str) -> Pred:
    return lambda path: S.layer_of(path) in layers


def is_replay(path: str) -> bool:
    """The model's forward run again inside the backward tick's vjp."""
    return S.phase_of(path) == S.REPLAY and S.layer_of(path) in \
        S.MODEL_LAYERS


def key_of(path: str) -> Tuple[str, str]:
    return (S.layer_of(path) or UNSCOPED, S.phase_of(path) or NO_PHASE)


def table(trace: T.Trace, smap: Dict[str, str]) -> Dict[str, object]:
    """Device seconds per layer and phase (the union of the leaf ops'
    intervals, averaged over the devices), the unscoped seconds, the
    share of busy time that scoped ops cover, and how many of the
    window's op names the map holds."""
    keys = sorted({key_of(smap.get(n, "")) for ev in trace.devices.values()
                   for n, _, _ in ev})
    nd = max(1, len(trace.devices))
    per: Dict[str, Dict[str, float]] = {}
    for lay, ph in keys:
        ns = sum(scope_ns(trace, smap, d,
                          lambda p, k=(lay, ph): key_of(p) == k)
                 for d in trace.devices)
        per.setdefault(lay, {})[ph] = ns / nd / 1e9
    busy = sum(trace.busy_ns(d) for d in trace.devices)
    scoped = sum(scope_ns(trace, smap, d,
                          lambda p: S.layer_of(p) is not None)
                 for d in trace.devices)
    names = {n for ev in trace.devices.values() for n, _, _ in ev}
    return {"seconds": per,
            "scoped_pct": 100.0 * scoped / busy if busy else None,
            "ops_mapped": [len(names & set(smap)), len(names)]}


def host_events(path: str) -> List[T.Event]:
    """Every event of the trace's host planes except the Python
    tracer's (``$``-prefixed function calls), as (name, start, end)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out: List[T.Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events
                           if not e.name.startswith("$"))
    return out


def named_gaps(trace: T.Trace, host: List[T.Event],
               smap: Optional[Dict[str, str]] = None, top: int = 10
               ) -> List[list]:
    """Device 0's ``top`` longest idle gaps in the window: the host event
    open at each gap's start that began last, the gap in seconds and,
    with a scope map, the layer and phase of the device op that ran last
    before it (``unscoped -`` at the window's start)."""
    if not trace.devices:
        return []
    ops = next(iter(trace.devices.values()))
    busy = T._union((a, b) for _, a, b in ops)
    edges = [trace.lo] + [x for iv in busy for x in iv] + [trace.hi]
    gaps = [(a, b - a) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: -g[1])
    out = []
    for t, length in gaps[:top]:
        open_ = [e for e in host if e[1] <= t <= e[2] and e[0] != "window"]
        gap = [max(open_, key=lambda e: e[1])[0] if open_ else "window",
               length / 1e9]
        if smap is not None:
            before = [e for e in ops if e[2] <= t]
            last = max(before, key=lambda e: e[2])[0] if before else ""
            gap.append(" ".join(key_of(smap.get(last, ""))))
        out.append(gap)
    return out


def _map_of_ops(trace: T.Trace, smap: Dict[str, str]) -> Dict[str, str]:
    names = {n for ev in trace.devices.values() for n, _, _ in ev}
    return {n: smap[n] for n in sorted(names) if n in smap}


def run_scoped(cell, seed: int, seconds: float, devices,
               record: Optional[str] = None) -> Dict[str, object]:
    """One traced window of ``cell``, as ``bench/run.py --trace 1`` runs
    it (``run.traced_window``); returns the cell's per-layer metrics and
    those of ``METRICS``, the table, the named gaps and the counters."""
    from bench import run as R
    from bench.cells import load_metric

    run = R.set_up(cell, seed, devices)
    base = os.path.join(record, f"{cell.name}.scoped") if record else None
    host: List[T.Event] = []

    def keep(tdir: str) -> None:
        path = T.find(tdir)
        host.extend(host_events(path))
        if base:
            os.makedirs(record, exist_ok=True)
            with open(path, "rb") as f, \
                    gzip.open(base + ".xplane.pb.gz", "wb") as g:
                shutil.copyfileobj(f, g)

    tr, ctx, wall = R.traced_window(cell, run, seconds, devices, keep)
    smap = ctx["scopes"]
    if base:
        with gzip.open(base + ".scopes.json.gz", "wt") as g:
            json.dump(_map_of_ops(tr, smap), g)
    names = [pl["name"] for pl in cell.per_layer] + list(METRICS)
    metrics = {name: load_metric(name)(tr, ctx) for name in names}
    tab = table(tr, smap)
    gaps = named_gaps(tr, host, smap)
    R.log(f"[trace] scopes {json.dumps(tab)}")
    R.log(f"[trace] idle gaps {json.dumps(gaps)}")
    n = ctx["steps"]
    return {"steps": n, "window_s": tr.window_s(), "busy_s": tr.busy_s(),
            "tokens_per_s": n * run["prog"].tokens_per_step / wall,
            "counters": ctx["counters"], "metrics": metrics, "scopes": tab,
            "idle_gaps": gaps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    from bench import run as R
    from bench.cells import resolve
    cell = resolve(args.workload)
    R.enable_cache()
    devices = R.tpu_devices(cell.chips)
    out = run_scoped(cell, args.seed, args.seconds, devices, args.record)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
