"""Reduction of a profiler trace to the numbers the per-layer metrics read.

`load` turns the ``.xplane.pb`` that `jax.profiler` writes into a plain
dict, the only form the rest of this module and the metric readers see:

    {"devices": {<plane>: {<line>: [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds each device plane (``/device:TPU:0``, ...) line by line;
``host`` holds the events of every host thread.  Device and host events
share the profiler's clock, so a host span bounds device work.  The same
dict, cut to a short window, is what the tests keep as a recorded trace.
"""
from __future__ import annotations

import glob
import gzip
import heapq
import json
import os
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

# the device line whose events are single operations (fusions, custom
# calls, copies), and the one whose events are whole programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(profile_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``profile_dir``, as a plain dict."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    out: Dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            out["devices"][plane.name] = {
                line.name: [[short_name(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, int(e.start_ns),
                                    int(e.duration_ns)]
                                   for e in line.events)
    return out


def short_name(name: str) -> str:
    """An operation's HLO instruction name: a v5e trace names each op by
    its whole HLO text, ``%fusion.12 = bf16[...] fusion(...), ...``."""
    return name.split(" = ", 1)[0].lstrip("%")


def kind(name: str) -> str:
    """The instruction name without its number: ``fusion.12`` → ``fusion``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def save(tr: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def read(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window_of(tr: Dict, span: str) -> Tuple[int, int]:
    """(start, end) ns of the longest host event named ``span``."""
    hits = [e for e in tr["host"] if e[0] == span]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    name, start, dur = max(hits, key=lambda e: e[2])
    return start, start + dur


def device_events(tr: Dict, line: str, win: Tuple[int, int],
                  match: Optional[Callable[[str], bool]] = None
                  ) -> Dict[str, List[List]]:
    """Per device plane, the events of ``line`` that lie inside ``win``
    (clipped to it) and whose name ``match`` accepts."""
    lo, hi = win
    out = {}
    for plane, lines in tr["devices"].items():
        evs = []
        for name, start, dur in lines.get(line, []):
            s, e = max(start, lo), min(start + dur, hi)
            if e > s and (match is None or match(name)):
                evs.append([name, s, e - s])
        out[plane] = evs
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_intervals(tr: Dict, win: Tuple[int, int]) -> Dict[str, List]:
    """Per device plane, the union of its operations' intervals in ``win``."""
    return {plane: _union([(s, s + d) for _, s, d in evs])
            for plane, evs in device_events(tr, OPS_LINE, win).items()}


def busy_ns(tr: Dict, win: Tuple[int, int]) -> float:
    """Busy nanoseconds in ``win``, averaged over the device planes that
    ran an operation."""
    per = [sum(e - s for s, e in iv)
           for iv in busy_intervals(tr, win).values() if iv]
    return sum(per) / len(per) if per else 0.0


def total_ns(tr: Dict, line: str, win: Tuple[int, int],
             match: Callable[[str], bool]) -> float:
    """Summed duration of the matching events of ``line`` in ``win``,
    averaged over the device planes that have any."""
    per = [sum(d for _, _, d in evs)
           for evs in device_events(tr, line, win, match).values() if evs]
    return sum(per) / len(per) if per else 0.0


def _host_names(host: List[List], times: List[int]) -> Dict[int, str]:
    """For each time, the name of the innermost (shortest) host event
    that spans it: one sweep over the events in start order."""
    evs = sorted((s, s + d, d, name) for name, s, d in host if d > 0)
    out: Dict[int, str] = {}
    active: List[Tuple[int, int, str]] = []
    i = 0
    for t in sorted(set(times)):
        while i < len(evs) and evs[i][0] <= t:
            heapq.heappush(active, (evs[i][1], evs[i][2], evs[i][3]))
            i += 1
        while active and active[0][0] <= t:
            heapq.heappop(active)
        out[t] = min(active, key=lambda a: a[1])[2] if active \
            else "(no host event)"
    return out


def leaves(evs: List[List]) -> List[List]:
    """The events that hold no other: a v5e trace nests a loop's body ops
    inside the ``while`` op's own event."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def breakdown(tr: Dict, win: Tuple[int, int], top: int = 10) -> Dict:
    """Where the window went: device time by kind of operation (leaf
    events only, so a loop is not counted with its body), and the device's
    idle time by what the host was doing at the middle of each gap; in
    seconds, averaged over the device planes that ran an operation."""
    by_plane = {p: evs for p, evs in device_events(tr, OPS_LINE, win).items()
                if evs}
    n = max(len(by_plane), 1)
    ops: Dict[str, float] = defaultdict(float)
    for evs in by_plane.values():
        for name, _, dur in leaves(evs):
            ops[kind(name)] += dur * 1e-9 / n
    gaps = []
    for evs in by_plane.values():
        iv = _union([(s, s + d) for _, s, d in evs])
        edges = [win[0]] + [x for s, e in iv for x in (s, e)] + [win[1]]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    names = _host_names(tr["host"], [(a + b) // 2 for a, b in gaps])
    idle: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        idle[names[(a + b) // 2]] += (b - a) * 1e-9 / n

    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"device_ops": head(ops), "idle_gaps": head(idle)}
