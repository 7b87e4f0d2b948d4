"""The ``read_mb_per_s`` reader on hand-built traces: the rate of the
``repro.read`` spans that start in the window, and nothing (no error) on a
trace whose program emits its other spans but no ``read``, as a program
from before that span does."""
from pathlib import Path

import pytest

import harness
from progtrace import ProgramTrace

READER = Path(__file__).resolve().parent / "metrics" / "read_mb_per_s.py"

#: a window 0..10 s; reads of 2 strips start in it (0.5-0.9 s, 120 MB, and
#: 4-4.6 s, 90 MB, on two prefetch threads), a third starts after it
SPANS = [
    ("describe", 0.4, 0.5, {"row0": 0, "col0": 0}),
    ("read", 0.5, 0.9, {"row0": 0, "col0": 0, "bytes": 120_000_000, "inputs": 2}),
    ("d2h", 1.0, 1.2, {"row0": 0, "col0": 0, "bytes": 1_000}),
    ("read", 4.0, 4.6, {"row0": 256, "col0": 0, "bytes": 90_000_000, "inputs": 2}),
    ("read", 10.2, 10.4, {"row0": 512, "col0": 0, "bytes": 5_000_000, "inputs": 2}),
]


def _read(program):
    tr = ProgramTrace({"0": [("fusion.1", 1.0, 2.0)]}, [("window", 0.0, 10.0)],
                      program=program)
    ctx = harness.Context(trace=tr, window=tr.window(), program_trace=tr)
    return harness.load_module(READER, "m_read_mb_per_s").read(ctx)


def test_rate_of_the_reads_in_the_window():
    assert _read(SPANS) == pytest.approx(210.0 / 1.0)


def test_nothing_without_read_spans():
    assert _read([e for e in SPANS if e[0] != "read"]) is None
    assert _read([e for e in SPANS if e[1] > 10.0]) is None  # none starts in the window
