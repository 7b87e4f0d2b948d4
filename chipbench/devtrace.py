"""Reduce a profiler trace to the numbers the per-layer metrics read.

``Trace`` holds, on one clock in seconds, the device operations of each
chip and the benchmark's own host spans (``chipbench.<kind>`` annotations,
see ``timed.py``).  Each ``/device:TPU:<n>`` plane has an ``XLA Ops`` line
(the operations that occupy the chip, one at a time) and an ``Async XLA
Ops`` line (asynchronous starts, such as ``collective-permute-start``,
overlapping the former).  An event's name is its HLO instruction text,
``%glcm_haralick.1 = f32[...] custom-call(...)``; ``op_name`` cuts it to
``glcm_haralick.1`` and ``op_family`` to ``glcm_haralick``, the kernel's
``name=``.  The trace is built from the profiler's ``.xplane.pb`` or from
the plain JSON that ``Trace.to_json`` writes (the recorded test traces).

Every reduction works inside a window ``(lo, hi)``:

- busy time: the union of one chip's ``XLA Ops`` intervals, averaged over
  chips;
- kernel time: the summed durations, over chips, of ``XLA Ops`` events of
  the kernel's family; none at all raises ``MissingEvents``;
- collective time: the union, per chip, of collective events on either
  line, averaged over chips; none at all raises ``MissingEvents``;
- idle gaps: the holes between busy intervals on chip 0, each named after
  the benchmark host span that covers it.
"""
from __future__ import annotations

import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "chipbench."
COLLECTIVES = (
    "collective-permute", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all",
)
#: host span kinds, most specific first: a gap is named after the first
#: kind in this order that covers over half of it
SPAN_ORDER = ("read", "write", "pass", "window")


class MissingEvents(LookupError):
    """A reduction found none of the events it reads."""


def op_name(event_name: str) -> str:
    """``%copy.5 = u16[...] copy(...)`` -> ``copy.5``; a bare name stays."""
    return event_name.lstrip("%").split(" ", 1)[0]


def op_family(event_name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; names without a numeric suffix
    stay as they are."""
    name = op_name(event_name)
    head, dot, tail = name.rpartition(".")
    return head if dot and tail.isdigit() else name


class Trace:
    def __init__(self, devices: Dict[str, List[Event]], spans: List[Event],
                 async_ops: Optional[Dict[str, List[Event]]] = None):
        #: chip id -> [(HLO event name, start s, end s)] of ``XLA Ops``
        self.devices = {k: sorted(v, key=lambda e: e[1]) for k, v in devices.items()}
        #: chip id -> the same for ``Async XLA Ops``
        self.async_ops = {
            k: sorted(v, key=lambda e: e[1]) for k, v in (async_ops or {}).items()
        }
        #: [(kind, start s, end s)] of the benchmark's host spans
        self.spans = sorted(spans, key=lambda e: e[1])

    # -- loading -------------------------------------------------------------
    @classmethod
    def from_xplane(cls, path: Path) -> "Trace":
        import jax

        data = jax.profiler.ProfileData.from_file(str(path))
        lines = {OPS_LINE: {}, ASYNC_LINE: {}}
        spans: list = []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PLANE):
                chip = plane.name[len(DEVICE_PLANE):]
                for line in plane.lines:
                    if line.name in lines:
                        lines[line.name][chip] = [
                            (ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events
                        ]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((
                                ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                            ))
        return cls(lines[OPS_LINE], spans, lines[ASYNC_LINE])

    @classmethod
    def from_json(cls, path: Path) -> "Trace":
        raw = json.loads(Path(path).read_text())

        def events(d):
            return {k: [tuple(e) for e in v] for k, v in d.items()}

        return cls(events(raw["devices"]), [tuple(e) for e in raw["spans"]],
                   events(raw.get("async_ops", {})))

    def to_json(self, path: Path, window: Interval) -> None:
        """Write the part of the trace inside ``window`` as plain JSON."""
        lo, hi = window

        def cut(evs):
            return [e for e in evs if e[2] > lo and e[1] < hi]

        Path(path).write_text(json.dumps({
            "devices": {k: cut(v) for k, v in self.devices.items()},
            "async_ops": {k: cut(v) for k, v in self.async_ops.items()},
            "spans": cut(self.spans),
        }))

    # -- windows ---------------------------------------------------------------
    def window(self, kind: str = "window") -> Interval:
        """The first host span of ``kind``: the measured window."""
        for k, a, b in self.spans:
            if k == kind:
                return a, b
        raise MissingEvents(f"no host span chipbench.{kind}")

    # -- reductions ------------------------------------------------------------
    def busy_intervals(self, chip: str, window: Interval) -> List[Interval]:
        return merge([(a, b) for _, a, b in self.devices.get(chip, [])], window)

    def busy_s(self, window: Interval) -> float:
        """Union of op intervals in the window, averaged over chips."""
        if not self.devices:
            raise MissingEvents("no device plane in the trace")
        return sum(
            total(self.busy_intervals(d, window)) for d in self.devices
        ) / len(self.devices)

    def kernel_seconds(self, kernel: str, window: Interval) -> Tuple[float, int]:
        """Device time of the kernel's events in the window, summed over
        chips, and the number of those events."""
        lo, hi = window
        secs, n = 0.0, 0
        for ops in self.devices.values():
            for name, a, b in ops:
                if b > lo and a < hi and op_family(name) == kernel:
                    secs += min(b, hi) - max(a, lo)
                    n += 1
        if n == 0:
            raise MissingEvents(f"no device op of kernel {kernel}")
        return secs, n

    def collective_seconds(self, window: Interval,
                           prefixes: Sequence[str] = COLLECTIVES) -> float:
        """Union, per chip, of the collective events on both lines, averaged
        over chips."""
        chips = set(self.devices) | set(self.async_ops)
        per_chip, found = [], False
        for chip in chips:
            evs = [
                (a, b)
                for line in (self.devices, self.async_ops)
                for name, a, b in line.get(chip, [])
                if op_name(name).startswith(tuple(prefixes))
            ]
            found = found or bool(merge(evs, window))
            per_chip.append(total(merge(evs, window)))
        if not found:
            raise MissingEvents(f"no device op named {'/'.join(prefixes)}*")
        return sum(per_chip) / len(per_chip)

    def top_ops(self, window: Interval, n: int = 10) -> List[List]:
        """Op families by summed device time in the window, averaged over
        chips."""
        lo, hi = window
        acc: Dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for name, a, b in ops:
                if b > lo and a < hi:
                    acc[op_family(name)] += min(b, hi) - max(a, lo)
        k = max(1, len(self.devices))
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / k] for name, secs in ranked]

    def idle_gaps(self, window: Interval, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first chip in the window, each
        named after the benchmark host span that covers it."""
        chip = sorted(self.devices)[0]
        lo, hi = window
        gaps, t = [], lo
        for a, b in self.busy_intervals(chip, window):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label(g), g[1] - g[0]] for g in gaps[:n]]

    def label(self, gap: Interval) -> str:
        """The first kind of ``SPAN_ORDER`` whose spans cover over half of
        the gap; else the kind covering most of it; else ``outside``."""
        covers = [(kind, self.span_busy_s(kind, gap)) for kind in SPAN_ORDER]
        for kind, cover in covers:
            if cover > 0.5 * (gap[1] - gap[0]):
                return kind
        kind, cover = max(covers, key=lambda kc: kc[1])
        return kind if cover > 0 else "outside"

    def span_busy_s(self, kind: str, window: Interval) -> float:
        return total(merge([(a, b) for k, a, b in self.spans if k == kind], window))


def merge(intervals, window: Interval) -> List[Interval]:
    """Union of intervals, clipped to the window, as sorted disjoint pairs."""
    lo, hi = window
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)
