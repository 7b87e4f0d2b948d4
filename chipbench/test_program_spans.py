"""The program's spans in the trace reduction (``progtrace.py``) and the
readers of the per-layer metrics built on them: on a hand-made trace with
known answers, on an excerpt of a trace recorded on a TPU v5e
(``testdata/s2-textures-program.json``), and on a tiny traced run of the
harness on the CPU."""
from pathlib import Path

import pytest

import harness
from devtrace import MissingEvents, Trace
from progtrace import ProgramTrace, program_trace

DATA = Path(__file__).resolve().parent / "testdata"
DISPATCH = ("begin", "wait_inputs", "dispatch", "d2h", "wait_write", "end")
METRICS = ("idle_write_pct", "idle_inputs_pct", "idle_dispatch_pct", "idle_d2h_pct",
           "d2h_gb_per_s", "describe_us", "write_mb_per_s")

#: one chip, a window 0..10 s, busy 1.5-2 and 3.5-4.5.  The dispatching
#: thread: begin 0-1, wait_inputs 1-1.2, dispatch 1.2-2, d2h 2-3, wait_write
#: 3-3.4, wait_inputs 3.4-3.5, dispatch 3.5-4.6, d2h 4.6-6, wait_write
#: 6-8.8, nothing 8.8-9, end 9-10.5 (past the window).  Other threads:
#: describe 0.5-1.2 and 3-3.8, consume 3.2-7 and flush 9-10.5, overlapping
#: the dispatching thread's spans.
HAND = ProgramTrace(
    {"0": [("fusion.1", 1.5, 2.0), ("fusion.2", 3.5, 4.5)]},
    [("window", 0.0, 10.0)],
    program=[
        ("begin", 0.0, 1.0, {}),
        ("wait_inputs", 1.0, 1.2, {"row0": 0, "col0": 0}),
        ("dispatch", 1.2, 2.0, {"row0": 0, "col0": 0}),
        ("d2h", 2.0, 3.0, {"row0": 0, "col0": 0, "bytes": 3_000_000_000}),
        ("wait_write", 3.0, 3.4, {"row0": 0, "col0": 0}),
        ("wait_inputs", 3.4, 3.5, {"row0": 8, "col0": 0}),
        ("dispatch", 3.5, 4.6, {"row0": 8, "col0": 0}),
        ("d2h", 4.6, 6.0, {"row0": 8, "col0": 0, "bytes": 1_000_000_000}),
        ("wait_write", 6.0, 8.8, {}),
        ("end", 9.0, 10.5, {}),
        ("describe", 0.5, 1.2, {"row0": 0, "col0": 0}),
        ("describe", 3.0, 3.8, {"row0": 8, "col0": 0}),
        ("consume", 3.2, 7.0, {"row0": 0, "col0": 0, "bytes": 300_000_000}),
        ("flush", 9.0, 10.5, {"bytes": 150_000_000}),
    ],
)


def ctx_of(tr: ProgramTrace) -> harness.Context:
    return harness.Context(trace=tr, window=tr.window(), program_trace=tr)


def read(name, ctx):
    return harness.load_module(
        Path(__file__).resolve().parent / "metrics" / f"{name}.py", f"m_{name}"
    ).read(ctx)


def test_idle_split_partitions_the_idle_time():
    w = HAND.window()
    idle = 10.0 - HAND.busy_s(w)
    assert idle == pytest.approx(8.5)
    parts = {n: HAND.idle_within({n}, w) for n in DISPATCH}
    # dispatch holds the busy time but only its idle edges count; end is
    # clipped at 10
    assert parts == {"begin": pytest.approx(1.0), "wait_inputs": pytest.approx(0.3),
                     "dispatch": pytest.approx(0.4), "d2h": pytest.approx(2.4),
                     "wait_write": pytest.approx(3.2), "end": pytest.approx(1.0)}
    assert HAND.idle_within(DISPATCH, w) == pytest.approx(sum(parts.values()))
    assert idle - sum(parts.values()) == pytest.approx(0.2)  # 8.8-9, under no span


def test_overlap_across_threads_counts_once():
    w = HAND.window()
    # consume (another thread) overlaps wait_write and dispatch: the union
    # 3-8.8 counts once, less the busy 3.5-4.5
    assert HAND.idle_within({"wait_write", "consume"}, w) == pytest.approx(5.8 - 1.0)
    assert HAND.idle_within({"describe"}, w) == pytest.approx(0.7 + 0.5)


def test_clipping_to_the_window():
    assert HAND.idle_within({"end", "flush"}, (0.0, 9.5)) == pytest.approx(0.5)
    assert HAND.idle_within({"begin"}, (0.5, 3.0)) == pytest.approx(0.5)
    assert HAND.program_spans({"d2h"}, (4.0, 10.0)) == [
        ("d2h", 4.6, 6.0, {"row0": 8, "col0": 0, "bytes": 1_000_000_000})]
    with pytest.raises(MissingEvents):
        HAND.program_spans({"d2h"}, (6.5, 10.0))  # none starts there


def test_missing_program_spans_raise():
    bare = ProgramTrace(HAND.devices, HAND.spans)
    with pytest.raises(MissingEvents):
        bare.idle_within({"d2h"}, bare.window())
    with pytest.raises(MissingEvents):
        HAND.idle_within({"no_such_span"}, HAND.window())
    with pytest.raises(MissingEvents):
        ProgramTrace({}, [("window", 0.0, 1.0)], program=HAND.program).idle_within(
            {"d2h"}, (0.0, 1.0))


def test_readers_on_the_hand_trace():
    ctx = ctx_of(HAND)
    got = {m: read(m, ctx) for m in METRICS}
    assert got == {
        "idle_write_pct": pytest.approx(100 * (1.0 + 3.2 + 1.0) / 10),
        "idle_inputs_pct": pytest.approx(100 * 0.3 / 10),
        "idle_dispatch_pct": pytest.approx(100 * 0.4 / 10),
        "idle_d2h_pct": pytest.approx(100 * 2.4 / 10),
        "d2h_gb_per_s": pytest.approx(4.0 / 2.4),
        "describe_us": pytest.approx(1e6 * (0.7 + 0.8) / 2),
        "write_mb_per_s": pytest.approx(450.0 / (3.8 + 1.5)),
    }


def _profile(root: Path, with_spans: bool) -> harness.Context:
    """A tiny profile under ``root/<cell>`` with a window span, and with or
    without a program span in it; the context a reader of it is given."""
    import jax

    from repro.core.tracing import span

    with jax.profiler.trace(str(root / "cell")):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            if with_spans:
                with span("d2h", row0=0, col0=0, bytes=8):
                    pass
    tr = Trace.from_xplane(harness._xplane(root / "cell"))
    return harness.Context(trace=tr, window=tr.window())


def test_readers_find_the_profile_of_their_run(tmp_path, monkeypatch):
    import progtrace

    monkeypatch.setattr(progtrace, "TRACES", tmp_path)
    ctx = _profile(tmp_path, with_spans=True)
    tr = program_trace(ctx)
    assert tr is ctx.program_trace and tr.window() == ctx.window
    assert [(n, st) for n, _, _, st in tr.program] == [
        ("d2h", {"row0": 0, "col0": 0, "bytes": 8})]
    with pytest.raises(LookupError):  # another run's profile
        program_trace(harness.Context(trace=ctx.trace, window=(0.0, 1.0)))


def test_a_program_without_spans_reads_nothing(tmp_path, monkeypatch):
    """Traced without any span, this program's run ends; a checkout whose
    program has no span helper (the benchmark laid over an older program)
    reports none of these metrics, and raises nothing."""
    import progtrace

    monkeypatch.setattr(progtrace, "TRACES", tmp_path)
    ctx = _profile(tmp_path, with_spans=False)
    with pytest.raises(MissingEvents):
        read("idle_d2h_pct", ctx)
    monkeypatch.setattr(progtrace, "_program_has_spans", lambda: False)
    ctx = harness.Context(trace=ctx.trace, window=ctx.window)
    assert [read(m, ctx) for m in METRICS] == [None] * len(METRICS)


def test_json_round_trip(tmp_path):
    HAND.to_json(tmp_path / "t.json", (0.0, 5.0))
    back = ProgramTrace.from_json(tmp_path / "t.json")
    assert back.program == [e for e in HAND.program if e[1] < 5.0]
    assert back.devices == HAND.devices
    # a recorded file without program spans loads as before
    old = ProgramTrace.from_json(DATA / "s2-convert.json")
    assert old.program == []
    assert old.devices == Trace.from_json(DATA / "s2-convert.json").devices


#: the readings printed on the chip for the recorded excerpt: four strips,
#: 1.25 s, in the first third of an s2-textures pass on a TPU v5e
RECORDED = {
    "idle_write_pct": 85.38304950321604,
    "idle_inputs_pct": 0.0,
    "idle_dispatch_pct": 0.5874064222339501,
    "idle_d2h_pct": 8.34004637062097,
    "d2h_gb_per_s": 2.150550468022896,
    "describe_us": 139.42000000011222,
    "write_mb_per_s": 175.15041206684083,
}


def test_recorded_excerpt():
    tr = ProgramTrace.from_json(DATA / "s2-textures-program.json")
    w = tr.window()
    ctx = ctx_of(tr)
    got = {m: read(m, ctx) for m in METRICS}
    assert got == {m: pytest.approx(v, rel=1e-9) for m, v in RECORDED.items()}
    # mid-pass: the dispatching thread's per-strip spans, which do not
    # overlap and leave under 2% of the window's idle time uncovered
    present = [n for n in DISPATCH if any(e[0] == n for e in tr.program)]
    assert present == ["wait_inputs", "dispatch", "d2h", "wait_write"]
    parts = sum(tr.idle_within({n}, w) for n in present)
    assert parts == pytest.approx(tr.idle_within(present, w))
    idle = (w[1] - w[0]) - tr.busy_s(w)
    assert 0 <= idle - parts < 0.02 * (w[1] - w[0])
    for n in ("describe", "wait_inputs", "dispatch", "d2h", "wait_write", "consume"):
        assert all({"row0", "col0"} <= set(st) for m, _, _, st in tr.program
                   if m == n and (n != "wait_write" or st))


def test_traced_harness_run_finds_its_spans(tiny_root, monkeypatch):
    """A tiny traced run on the CPU: ``program_trace`` finds the profile of
    the run, and every stage of the pass left its spans in it."""
    import jax

    import progtrace

    r = harness.Run(tiny_root, "s2-convert", 3, jax.devices(), 0.0)
    r.setup()
    r.window(0.2, True)
    r.free()
    r.sink.close()
    monkeypatch.setattr(progtrace, "TRACES", tiny_root / "chipbench" / "traces")
    tr = Trace.from_xplane(harness._xplane(r.trace_dir))
    ctx = harness.Context(trace=tr, window=tr.window("window"))
    pt = program_trace(ctx)
    assert pt is ctx.program_trace and pt.window() == ctx.window
    names = {n for n, _, _, _ in pt.program}
    assert names == set(DISPATCH) | {"describe", "consume", "flush"}
    lo, hi = ctx.window
    flush = sum(st["bytes"] for n, a, _, st in pt.program if n == "flush" and lo <= a < hi)
    consume = sum(st["bytes"] for n, a, _, st in pt.program if n == "consume" and lo <= a < hi)
    assert consume + flush == r.bytes_in_window
