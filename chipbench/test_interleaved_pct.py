"""The ``interleaved_pct`` reader on hand-built traces: the share of the
``repro.consume`` bytes that carried an ``interleaved`` stat's bytes, over
the spans that start in the window, and nothing (no error) on a trace whose
``consume`` spans carry no ``interleaved`` stat, as a program from before
that stat emits them."""
from pathlib import Path

import pytest

import harness
from progtrace import ProgramTrace

READER = Path(__file__).resolve().parent / "metrics" / "interleaved_pct.py"

#: a window 0..10 s; two regions are consumed in it (400 MB written, 150 MB
#: of it interleaved first), a third starts after it
SPANS = [
    ("d2h", 1.0, 1.2, {"row0": 0, "col0": 0, "bytes": 1_000}),
    ("consume", 1.3, 2.0, {"row0": 0, "col0": 0, "bytes": 300_000_000,
                           "ranges": 40, "interleaved": 150_000_000}),
    ("consume", 4.0, 4.5, {"row0": 256, "col0": 0, "bytes": 100_000_000,
                           "ranges": 20, "interleaved": 0}),
    ("consume", 10.2, 10.6, {"row0": 512, "col0": 0, "bytes": 100_000_000,
                             "ranges": 20, "interleaved": 100_000_000}),
    ("flush", 10.6, 10.7, {"bytes": 5_000}),
]


def _without_stat(spans):
    return [(n, a, b, {k: v for k, v in st.items() if k != "interleaved"})
            for n, a, b, st in spans]


def _read(program):
    tr = ProgramTrace({"0": [("fusion.1", 1.0, 2.0)]}, [("window", 0.0, 10.0)],
                      program=program)
    ctx = harness.Context(trace=tr, window=tr.window(), program_trace=tr)
    return harness.load_module(READER, "m_interleaved_pct").read(ctx)


def test_share_of_the_consumed_bytes_in_the_window():
    assert _read(SPANS) == pytest.approx(100.0 * 150 / 400)


def test_zero_when_every_region_came_c_ordered():
    spans = [(n, a, b, dict(st, interleaved=0) if n == "consume" else st)
             for n, a, b, st in SPANS]
    assert _read(spans) == 0.0


def test_nothing_without_the_stat():
    assert _read(_without_stat(SPANS)) is None
    assert _read([e for e in SPANS if e[0] != "consume"]) is None
    assert _read([e for e in SPANS if e[1] > 10.0]) is None  # none starts in the window
