"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read. Reads the file through ``jax.profiler.ProfileData`` alone.

- the traced window: the host span :data:`WINDOW_SPAN` that the harness
  opens around the measured window;
- device busy time: the union of the op intervals on each device
  plane's ``XLA Ops`` line inside the window, averaged over the devices
  that ran anything, and the idle share ``1 - busy / window``;
- device time per kernel: op events summed by op name, the HLO suffix
  (``.12``) and the text after ``=`` taken off; and per program: the
  ``XLA Modules`` line's events summed by module name, its ``(id)``
  taken off;
- host spans by name: durations of the named spans (``layer.name``, the
  repo's ``@instrument`` and the harness's own) inside the window;
- idle gaps: each stretch inside the window with no op on the device,
  put down to the named host span that covers most of it.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

#: the harness's span around the measured window
WINDOW_SPAN = "bench.window"
#: named spans: lower-case dotted names, as ``@instrument`` gives them
_SPAN_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
_OP_RE = re.compile(r"^%?([^\s=(]+)")
_SUFFIX_RE = re.compile(r"\.\d+$")
NO_SPAN = "(no span)"


def op_name(event_name: str) -> str:
    """``'%fused_l2_group_topk_packed.1 = (f32[...]) ...'`` ->
    ``'fused_l2_group_topk_packed'``."""
    m = _OP_RE.match(event_name)
    name = m.group(1) if m else event_name
    return _SUFFIX_RE.sub("", name)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernels: Dict[str, float]          # op name -> device seconds
    modules: Dict[str, float]          # program name -> device seconds
    spans: Dict[str, List[float]]      # span name -> durations, s
    idle_gaps: List[Tuple[str, float]]  # (host span, idle seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.kernels.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} .xplane.pb under {log_dir}")
    return files[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _label(gap: Tuple[int, int], spans: List[Tuple[int, int, str]],
           starts: List[int], longest: int) -> str:
    """The named span covering most of ``gap``; the shorter on a tie.
    ``spans`` are sorted by start, ``starts`` are their starts and
    ``longest`` the longest span's duration."""
    best, best_key = NO_SPAN, (0, 0)
    i = bisect.bisect_left(starts, gap[0] - longest)
    j = bisect.bisect_left(starts, gap[1])
    for s, e, name in spans[i:j]:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > 0:
            key = (cover, -(e - s))
            if key > best_key:
                best, best_key = name, key
    return best


def reduce(path: str, window_span: str = WINDOW_SPAN) -> Summary:
    """The summary of the trace at ``path`` (a file, or a directory
    holding one ``.xplane.pb``)."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_profile(jax.profiler.ProfileData.from_file(path),
                          window_span)


def reduce_profile(data, window_span: str = WINDOW_SPAN) -> Summary:
    """The summary of a ``jax.profiler.ProfileData``."""
    ops_by_dev: Dict[str, List[Tuple[int, int, str]]] = {}
    mods: List[Tuple[int, int, str]] = []
    host: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs += [(int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [(int(e.start_ns), int(e.end_ns),
                              e.name.split("(")[0]) for e in line.events]
            if evs:
                ops_by_dev[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(int(e.start_ns), int(e.end_ns), e.name)
                         for e in line.events if _SPAN_RE.match(e.name)]
    win = [(s, e) for s, e, n in host if n == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = win[0]
    window_ns = hi - lo
    if not ops_by_dev:
        raise ValueError("no device ops in the trace")
    spans = sorted((s, e, n) for s, e, n in host
                   if n != window_span and e > lo and s < hi)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    durations: Dict[str, List[float]] = collections.defaultdict(list)
    for s, e, n in spans:
        if s >= lo and e <= hi:
            durations[n].append((e - s) / 1e9)
    kernels: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0
    gaps_by: Dict[str, float] = collections.defaultdict(float)
    for evs in ops_by_dev.values():
        clipped = []
        for s, e, n in evs:
            if e > lo and s < hi:
                s2, e2 = max(s, lo), min(e, hi)
                clipped.append((s2, e2))
                kernels[op_name(n)] += (e2 - s2) / 1e9
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps_by[_label((g0, g1), spans, starts,
                                       longest)] += (g1 - g0) / 1e9
    n_dev = len(ops_by_dev)
    modules: Dict[str, float] = collections.defaultdict(float)
    for s, e, n in mods:
        if e > lo and s < hi:
            modules[n] += (min(e, hi) - max(s, lo)) / 1e9 / n_dev
    gaps = sorted(((k, v / n_dev) for k, v in gaps_by.items()),
                  key=lambda kv: -kv[1])
    return Summary(window_s=window_ns / 1e9,
                   busy_s=busy_total / n_dev / 1e9,
                   kernels={k: v / n_dev for k, v in kernels.items()},
                   modules=dict(modules),
                   spans=dict(durations), idle_gaps=gaps)

