"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

A trace holds one plane per chip (``/device:TPU:0`` ...), one for the
host (``/host:CPU``) and a few others that hold no operations. On a device plane the line ``XLA Ops`` holds one
event per operation that ran, with its start and duration in nanoseconds
on the host's clock. Host lines hold the TraceMe events of host threads,
among them the benchmark's own ``bench.*`` annotations.

The reduction is a pure function of event lists, so that a hand-built case
checks it as well as a recorded trace:

* busy time: the union of a device's operation intervals inside the
  window, averaged over the devices used;
* operation time: the summed durations of each named operation, so that a
  kernel's time is that of the operations whose name matches its pattern;
* idle gaps: the stretches of the window in which the device ran nothing,
  each named by what the host was doing then: the shortest host event that
  covers at least half of the gap;
* span time: the summed wall time and the number of each named host
  event inside the window, so that the program's own phases (the
  ``devdag.*`` annotations of ``repro.core.telemetry.phase``) and the
  benchmark's ``bench.*`` ones are read from the same clock.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")  # not e.g. /device:CUSTOM:...
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
WINDOW_EVENT = "bench.window"
NO_HOST_EVENT = "(no host event)"
NAME_CHARS = 160  # a TPU operation is named by its whole HLO instruction

Event = tuple[str, int, int]  # name, start_ns, end_ns


@dataclass
class Reduction:
    """What one traced window reduces to; times in seconds."""

    window_s: float
    busy_s: float        # union of operation intervals, mean over the devices used
    op_seconds: dict     # operation name -> summed duration over devices
    op_counts: dict      # operation name -> number of events
    idle_gaps: list      # [[host event, seconds]], most first, at most ``top``
    top: int = 10
    span_seconds: dict = field(default_factory=dict)  # host event name -> summed seconds
    span_counts: dict = field(default_factory=dict)   # host event name -> events

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def ops(self) -> list:
        """``[[operation, seconds]]``, most first, at most ``top``; names cut to NAME_CHARS."""
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k[:NAME_CHARS], v] for k, v in ranked[:self.top]]

    def matching(self, pattern: str) -> tuple[float, int]:
        """Summed seconds and event count of operations whose name matches."""
        rx = re.compile(pattern)
        hits = [k for k in self.op_seconds if rx.search(k)]
        return (sum(self.op_seconds[k] for k in hits),
                sum(self.op_counts[k] for k in hits))


def find_xplane(log_dir: str | Path) -> Path:
    """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def _profile(path: str | Path):
    """The trace as ``jax.profiler.ProfileData``; a ``.gz`` file is unpacked in memory."""
    import gzip

    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        return ProfileData.from_serialized_xspace(gzip.decompress(Path(path).read_bytes()))
    return ProfileData.from_file(str(path))


def load(path: str | Path) -> tuple[dict[str, list[Event]], list[Event]]:
    """Device operations per device plane, and every host event, from an ``.xplane.pb``."""
    pd = _profile(path)
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(e.name, int(e.start_ns), int(e.end_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith(HOST_PREFIX):
            host.extend((e.name, int(e.start_ns), int(e.end_ns))
                        for line in plane.lines for e in line.events)
    return devices, host


def window_of(host: list[Event]) -> tuple[int, int]:
    """The benchmark's measured window, from its ``bench.window`` annotation."""
    spans = [(s, e) for name, s, e in host if name == WINDOW_EVENT]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_EVENT!r} host event, found {len(spans)}")
    return spans[0]


def _clip(events, lo: int, hi: int):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def _union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _labeller(host: list[Event]):
    """``label(gap)``: the shortest host event covering half of the gap or more."""
    import numpy as np

    host = sorted(host, key=lambda ev: ev[2] - ev[1])
    starts = np.array([s for _, s, _ in host], np.int64)
    ends = np.array([e for _, _, e in host], np.int64)

    def label(gap: tuple[int, int]) -> str:
        s, e = gap
        cover = np.minimum(e, ends) - np.maximum(s, starts)
        hit = np.flatnonzero(2 * cover >= e - s)
        return host[hit[0]][0] if len(hit) else NO_HOST_EVENT

    return label


def reduce(devices: dict[str, list[Event]], host: list[Event],
           window: tuple[int, int], top: int = 10) -> Reduction:
    """Reduce device operations and host events inside ``window`` (ns).

    Host events are clipped to the window; one that straddles its edge
    counts once, with the part inside.

    Devices without a single operation in the window are not among the
    devices used; a window in which no device ran anything raises.
    """
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    host = list(_clip(host, lo, hi))
    label = _labeller(host)
    span_ns: dict[str, int] = defaultdict(int)
    span_counts: dict[str, int] = defaultdict(int)
    for name, s, e in host:
        span_ns[name] += e - s
        span_counts[name] += 1
    busy_ns, used = 0, 0
    op_ns: dict[str, int] = defaultdict(int)
    op_counts: dict[str, int] = defaultdict(int)
    gap_ns: dict[str, int] = defaultdict(int)
    for events in devices.values():
        events = list(_clip(events, lo, hi))
        if not events:
            continue
        used += 1
        busy = _union((s, e) for _, s, e in events)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in events:
            op_ns[name] += e - s
            op_counts[name] += 1
        for gap in _gaps(busy, lo, hi):
            gap_ns[label(gap)] += gap[1] - gap[0]
    if not used:
        raise ValueError("no device operation ran in the window")
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy_ns / used / 1e9,
                     op_seconds={k: v / 1e9 for k, v in op_ns.items()},
                     op_counts=dict(op_counts),
                     idle_gaps=[[k, v / 1e9] for k, v in gaps], top=top,
                     span_seconds={k: v / 1e9 for k, v in span_ns.items()},
                     span_counts=dict(span_counts))


def reduce_file(path: str | Path) -> Reduction:
    """``reduce`` over a recorded trace, in its ``bench.window``."""
    devices, host = load(path)
    return reduce(devices, host, window_of(host))


def describe(path: str | Path, top: int = 25) -> None:
    """Print a trace's planes and lines and its most frequent event names."""
    from collections import Counter

    pd = _profile(path)
    for plane in pd.planes:
        lines = [(line.name, len(list(line.events))) for line in plane.lines]
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for name, n in lines[:top]:
            print(f"  line {name!r}: {n} events")
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                names = Counter(e.name for e in line.events)
                print(f"  {line.name!r} top: {names.most_common(top)}")
    devices, host = load(path)
    print("host top:", Counter(name for name, _, _ in host).most_common(top))
    r = reduce(devices, host, window_of(host))
    print(f"window {r.window_s:.6f} s, busy {r.busy_s:.6f} s, idle {r.idle_share:.4%}")
    print("ops:", r.ops)
    print("idle gaps:", r.idle_gaps)


if __name__ == "__main__":
    import sys

    for arg in sys.argv[1:]:
        describe(arg)
