"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``load(dir)`` reads the one ``.xplane.pb`` under ``dir`` with
``jax.profiler.ProfileData`` and keeps:

* per device (a ``/device:TPU:<n>`` plane), the operations of its
  ``XLA Ops`` line as ``(name, start_ns, end_ns)``.  The name is the HLO
  instruction's own (``_gmm_jit.71``, ``fusion.625``, ``while.14``), not
  the whole instruction text the event carries; a kernel is found by the
  name of the jitted wrapper that launches it.  Control-flow ops (the
  executor's tick loop is one ``while``) enclose the ops they run, and
  span the gaps between them too; only the ops that enclose none
  (``leaves``) count as work;
* the host spans the harness writes (``window``, ``batch``,
  ``dispatch``, ``block``) as ``(name, start_ns, end_ns)``.

Both are on the profiler's one clock, so the host's ``window`` span bounds
the device operations that the window ran.  Everything a metric needs is a
method here: busy time (the union of the intervals of a device's leaf
operations inside the window), the events of a kernel, the time in which
only collectives run, and the
``breakdown`` of where the device time went and what the host was doing
while the device sat idle.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]

HOST_SPANS = ("window", "batch", "dispatch", "block")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SUFFIX = re.compile(r"(\.\d+|\.\.sunk|\.clone)+$")


def op_name(text: str) -> str:
    """``%_gmm_jit.71 = bf16[...] custom-call(...)`` -> ``_gmm_jit.71``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``_gmm_jit.71`` -> ``_gmm_jit``: the name without its numbering."""
    return SUFFIX.sub("", name)


def leaves(ev: List[Event]) -> List[Event]:
    """The ops that enclose no other op (in the order of ``ev``)."""
    order = sorted(range(len(ev)), key=lambda i: (ev[i][1], -ev[i][2]))
    parent = [False] * len(ev)
    stack: List[int] = []
    for i in order:
        while stack and ev[stack[-1]][2] <= ev[i][1]:
            stack.pop()
        if stack and ev[i][2] <= ev[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(ev, parent) if not p]


def _length(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in _union(intervals))


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: Iterable[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ev if b > lo and a < hi]


class Trace:
    def __init__(self, devices: Dict[int, List[Event]], host: List[Event]):
        self.host = sorted(host, key=lambda e: e[1])
        win = [e for e in self.host if e[0] == "window"]
        if not win:
            raise ValueError("the trace holds no 'window' span")
        self.lo, self.hi = win[0][1], win[0][2]
        self.devices = {d: leaves(_clip(ev, self.lo, self.hi))
                        for d, ev in sorted(devices.items())}

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_ns(self, dev: int) -> int:
        return _length((a, b) for _, a, b in self.devices[dev])

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns(d) for d in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self, dev: int) -> float:
        return 1.0 - self.busy_ns(dev) / (self.hi - self.lo)

    def events(self, match: Callable[[str], bool]) -> Dict[int, List[Event]]:
        return {d: [e for e in ev if match(e[0])]
                for d, ev in self.devices.items()}

    def alone_ns(self, dev: int, match: Callable[[str], bool]) -> int:
        """Time in which an op that ``match`` names runs on ``dev`` and no
        other op does."""
        ev = self.devices[dev]
        mine = _union((a, b) for n, a, b in ev if match(n))
        rest = _union((a, b) for n, a, b in ev if not match(n))
        both, j = 0, 0
        for a, b in mine:
            while j < len(rest) and rest[j][1] <= a:
                j += 1
            k = j
            while k < len(rest) and rest[k][0] < b:
                both += min(b, rest[k][1]) - max(a, rest[k][0])
                k += 1
        return sum(b - a for a, b in mine) - both

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The kinds of device op that took most time (summed over
        devices, in seconds), and the longest idle gaps of device 0 named
        by the harness span that was open when the gap began."""
        tot: Dict[str, int] = {}
        for ev in self.devices.values():
            for n, a, b in ev:
                tot[op_kind(n)] = tot.get(op_kind(n), 0) + b - a
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps: List[Tuple[str, float]] = []
        if self.devices:
            busy = _union((a, b) for _, a, b in
                          next(iter(self.devices.values())))
            edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((self._host_at(a), (b - a) / 1e9))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[n, v / 1e9] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in gaps[:top]]}

    def _host_at(self, t: int) -> str:
        """The innermost harness span open at ``t`` (the last to start)."""
        name = "none"
        for n, a, b in self.host:
            if a > t:
                break
            if b >= t and n != "window":
                name = n
        return "window" if name == "none" and self.lo <= t <= self.hi \
            else name


def roofline_share(events: Dict[int, List[Event]], flops: float,
                   nbytes: float, peak: Dict[str, float]) -> Optional[float]:
    """A kernel's share of its roofline, in %: the least time of its calls
    (each the larger of ``flops`` over the bf16 peak and ``nbytes`` over
    the HBM bandwidth) over the sum of their device durations."""
    n = sum(len(ev) for ev in events.values())
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    spent = sum(b - a for ev in events.values() for _, a, b in ev) / 1e9
    return 100.0 * n * least / spent


def from_file(path: str) -> Trace:
    """A trace from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events if e.name in HOST_SPANS)
    return Trace(devices, host)


def find(directory: str) -> Optional[str]:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(paths)[-1] if paths else None


def load(directory: str) -> Trace:
    path = find(directory)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_file(path)
