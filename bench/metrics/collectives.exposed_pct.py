"""collectives.exposed_pct: the share of the traced window in which a
collective runs on a chip and no other operation does, in %; the worst
chip's.  Collectives are found by their op names in the trace: the HLO
names (all-to-all, all-reduce, reduce-scatter, all-gather,
collective-permute, with their start and done halves) and the names the
program's ``shard_map`` collectives carry there (``psum``,
``psum_scatter``, ``all_to_all``, ``all_gather``, ``ppermute``)."""

import re

COLLECTIVE = re.compile(
    r"all[-_]to[-_]all|all[-_]reduce|reduce[-_]scatter|all[-_]gather"
    r"|collective[-_]permute|^psum|^ppermute")


def _is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def compute(trace, ctx):
    if not any(_is_collective(n) for ev in trace.devices.values()
               for n, _, _ in ev):
        return None
    win = trace.window_s() * 1e9
    return 100.0 * max(trace.alone_ns(d, _is_collective) / win
                       for d in trace.devices)
