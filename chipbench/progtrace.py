"""The program's own spans on the device's clock, and what the per-layer
metrics read from them.

The program marks its stages with profiler spans named ``repro.<name>``
(``src/repro/core/tracing.py`` lists them with their integer stats).
``ProgramTrace`` is a ``devtrace.Trace`` that also holds those spans as
``program``: ``(name without the prefix, start s, end s, stats dict)``, on
the clock of the device planes.  ``to_json`` and ``from_json`` carry them
under an optional ``"program"`` key, so a file without it still loads.

The idle attribution: ``idle_within(names, window)`` is the device's idle
time (the window minus the union of one chip's ``XLA Ops``, averaged over
chips) that lies inside the union of the named spans.  The spans of the
dispatching thread (``begin``, ``wait_inputs``, ``dispatch``, ``d2h``,
``wait_write``, ``end``) do not overlap, so their shares, plus the idle
time none of them covers, add up to the device's idle share.

``program_trace(ctx)`` finds the spans of the run a metric reader is given:
the newest profile under ``chipbench/traces``, checked to be the one the
harness reduced (same ``chipbench.window`` span).  A checkout whose program
has no span helper gives ``None``, so its readers report nothing; a program
that has it, traced without any of its spans, raises ``MissingEvents``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from devtrace import Interval, MissingEvents, Trace, merge, total

PROGRAM_PREFIX = "repro."
TRACES = Path(__file__).resolve().parent / "traces"

ProgramSpan = Tuple[str, float, float, Dict[str, int]]


class ProgramTrace(Trace):
    def __init__(self, devices, spans, async_ops=None,
                 program: Optional[List[ProgramSpan]] = None):
        super().__init__(devices, spans, async_ops)
        #: [(span name, start s, end s, stats)] of the program's spans
        self.program = sorted(program or [], key=lambda e: e[1])

    # -- loading -------------------------------------------------------------
    @classmethod
    def from_xplane(cls, path: Path) -> "ProgramTrace":
        import jax

        base = Trace.from_xplane(path)
        program = []
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        program.append((
                            ev.name[len(PROGRAM_PREFIX):], ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats),
                        ))
        return cls(base.devices, base.spans, base.async_ops, program)

    @classmethod
    def from_json(cls, path: Path) -> "ProgramTrace":
        base = Trace.from_json(path)
        raw = json.loads(Path(path).read_text())
        program = [(n, a, b, dict(st)) for n, a, b, st in raw.get("program", [])]
        return cls(base.devices, base.spans, base.async_ops, program)

    def to_json(self, path: Path, window: Interval) -> None:
        """Write the part of the trace inside ``window`` as plain JSON."""
        super().to_json(path, window)
        lo, hi = window
        raw = json.loads(Path(path).read_text())
        raw["program"] = [list(e) for e in self.program if e[2] > lo and e[1] < hi]
        Path(path).write_text(json.dumps(raw))

    # -- reductions ------------------------------------------------------------
    def program_spans(self, names: Iterable[str], window: Interval) -> List[ProgramSpan]:
        """The named spans that start in the window, whole; none at all
        raises ``MissingEvents``."""
        names, (lo, hi) = set(names), window
        found = [e for e in self.program if e[0] in names and lo <= e[1] < hi]
        if not found:
            raise MissingEvents(f"no program span {'/'.join(sorted(names))} in the window")
        return found

    def idle_within(self, names: Iterable[str], window: Interval) -> float:
        """Device idle time inside the union of the named spans, averaged
        over chips: per chip, |busy ∪ spans| − |busy| in the window."""
        names = set(names)
        cover = [(a, b) for n, a, b, _ in self.program if n in names]
        if not cover:
            raise MissingEvents(f"no program span {'/'.join(sorted(names))}")
        if not self.devices:
            raise MissingEvents("no device plane in the trace")
        idle = 0.0
        for chip in self.devices:
            busy = self.busy_intervals(chip, window)
            idle += total(merge(busy + cover, window)) - total(busy)
        return idle / len(self.devices)


def _program_has_spans() -> bool:
    return importlib.util.find_spec("repro.core.tracing") is not None


def program_trace(ctx) -> Optional[ProgramTrace]:
    """The program's spans of the run behind ``ctx`` (kept on it once
    read); ``None`` when the program has no span helper."""
    if "program_trace" in vars(ctx):
        return ctx.program_trace
    found = sorted(TRACES.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {TRACES}")
    tr = ProgramTrace.from_xplane(found[-1])
    if tr.window("window") != tuple(ctx.window):
        raise LookupError(f"{found[-1]} is not the trace of this run")
    if not tr.program:
        if _program_has_spans():
            raise MissingEvents("the program has spans, the trace holds none (repro.*)")
        tr = None
    ctx.program_trace = tr
    return tr


def idle_pct(ctx, names: Iterable[str]) -> Optional[float]:
    """Share of the window in which the device idled inside the named
    spans of the dispatching thread."""
    tr = program_trace(ctx)
    if tr is None:
        return None
    lo, hi = ctx.window
    return 100.0 * tr.idle_within(names, ctx.window) / (hi - lo)


def rate(ctx, names: Iterable[str], unit: float) -> Optional[float]:
    """Σ ``bytes`` over Σ duration of the named spans that start in the
    window, in ``unit`` bytes per second."""
    tr = program_trace(ctx)
    if tr is None:
        return None
    spans = tr.program_spans(names, ctx.window)
    return sum(st["bytes"] for _, _, _, st in spans) / sum(b - a for _, a, b, _ in spans) / unit
