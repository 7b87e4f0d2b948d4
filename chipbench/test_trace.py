"""The trace reduction, on a hand-made trace with known answers and on
excerpts of traces recorded on a TPU v5e (``testdata/``): one second of the
``s2-textures`` window, a quarter second of ``spot6-pansharpen`` and 0.8 s of
``s2-convert``, each with the benchmark's host spans and a ``window`` span
of its own."""
from pathlib import Path

import pytest

from devtrace import MissingEvents, Trace, merge, op_family, op_name, total

DATA = Path(__file__).resolve().parent / "testdata"

#: two chips, a window 0..10 s; chip 0 busy 1-3 and 2-4 (overlap) and 6-7,
#: chip 1 busy 0-10, its halo exchange started asynchronously at 4.5 and
#: waited for 5-6
HAND = Trace(
    {
        "0": [("%glcm_haralick.1 = f32[8,5]{1,0} custom-call(u16[8,4]{1,0} %p)", 1.0, 3.0),
              ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %a)", 2.0, 4.0),
              ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %b)", 6.0, 7.0),
              ("%copy.1 = f32[8]{0} copy(f32[8]{0} %c)", 12.0, 13.0)],
        "1": [("glcm_haralick", 0.0, 5.0),
              ("%collective-permute-done = u16[2,8]{1,0} collective-permute-done(%s)", 5.0, 6.0),
              ("fusion.3", 6.0, 10.0)],
    },
    [("window", 0.0, 10.0), ("pass", 0.5, 9.5), ("read", 4.2, 5.8),
     ("write", 7.5, 9.9)],
    {"1": [("%collective-permute-start = (u16[2,8]{1,0}) collective-permute-start(%x)",
            4.5, 5.5)]},
)


def test_hand_trace_reductions():
    w = HAND.window()
    assert w == (0.0, 10.0)
    assert HAND.busy_s(w) == pytest.approx((4.0 + 10.0) / 2)
    assert HAND.kernel_seconds("glcm_haralick", w) == (pytest.approx(7.0), 2)
    # chip 1: the union of 4.5-5.5 and 5-6; chip 0: none
    assert HAND.collective_seconds(w) == pytest.approx(1.5 / 2)
    ops = dict(HAND.top_ops(w))
    assert ops == {"fusion": pytest.approx(3.5), "glcm_haralick": pytest.approx(3.5),
                   "collective-permute-done": pytest.approx(0.5)}
    gaps = HAND.idle_gaps(w)
    # chip 0 idles 0-1 (pass covers half of it, the window all), 4-6 (read
    # covers 1.6 of 2) and 7-10 (write covers 2.4 of 3)
    assert [g[1] for g in gaps] == pytest.approx([3.0, 2.0, 1.0])
    assert [g[0] for g in gaps] == ["write", "read", "window"]


def test_missing_events_raise():
    with pytest.raises(MissingEvents):
        HAND.kernel_seconds("pansharpen_rcs", HAND.window())
    with pytest.raises(MissingEvents):
        Trace({"0": [("fusion", 0.0, 1.0)]}, []).collective_seconds((0.0, 1.0))
    with pytest.raises(MissingEvents):
        Trace({}, [("window", 0.0, 1.0)]).busy_s((0.0, 1.0))
    with pytest.raises(MissingEvents):
        Trace({"0": []}, []).window()


def test_helpers():
    assert merge([(3, 5), (0, 1), (0.5, 2), (9, 12)], (0, 10)) == [(0, 2), (3, 5), (9, 10)]
    assert total([(0, 2), (3, 5)]) == 4
    assert op_name("%copy.5 = u16[4]{0} copy(u16[4]{0} %x)") == "copy.5"
    assert op_family("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %a)") == "fusion"
    assert op_family("glcm_haralick") == "glcm_haralick"
    assert op_family("%add_bitcast_fusion = f32[8]{0} fusion()") == "add_bitcast_fusion"


#: recorded excerpt -> (kernel that runs in it, kernels that do not)
RECORDED = {
    "s2-textures": ("glcm_haralick", "pansharpen_rcs"),
    "spot6-pansharpen": ("pansharpen_rcs", "glcm_haralick"),
    "s2-convert": (None, "glcm_haralick"),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace(name):
    tr = Trace.from_json(DATA / f"{name}.json")
    lo, hi = w = tr.window()
    (chip, ops), = tr.devices.items()
    # busy time: the union of the op intervals, found here by a sweep
    inside = sorted((max(a, lo), min(b, hi)) for _, a, b in ops if b > lo and a < hi)
    busy, end = 0.0, lo
    for a, b in inside:
        busy += max(0.0, b - max(a, end))
        end = max(end, b)
    assert 0 < tr.busy_s(w) == pytest.approx(busy)
    # idle share: the gaps and the busy time fill the window exactly
    gaps = tr.idle_gaps(w, n=10_000)
    assert sum(g[1] for g in gaps) + busy == pytest.approx(hi - lo)
    assert {g[0] for g in gaps} <= {"read", "write", "pass", "window", "outside"}
    # the device op families are short HLO names, ranked by time
    top = tr.top_ops(w)
    assert all("%" not in f and " " not in f for f, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    runs, absent = RECORDED[name]
    if runs is not None:
        want = [(a, b) for n, a, b in ops
                if n.startswith(f"%{runs}.") and b > lo and a < hi]
        secs, n = tr.kernel_seconds(runs, w)
        assert n == len(want) > 0
        assert secs == pytest.approx(sum(min(b, hi) - max(a, lo) for a, b in want))
        assert top[0][0] == runs  # the kernel takes most of the device time
    with pytest.raises(MissingEvents):
        tr.kernel_seconds(absent, w)
    with pytest.raises(MissingEvents):
        tr.collective_seconds(w)  # one chip: no exchange


def test_kernel_roofline_without_its_events_ends_the_run():
    """A cell whose pipeline has the kernel, traced without any of its
    events, gets no share but an error; a cell without it reads nothing."""
    import harness

    tr = Trace.from_json(DATA / "s2-convert.json")
    ctx = harness.Context(
        trace=tr, window=tr.window(), pixels_in_window=1e6,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        kernel_work={"glcm_haralick": (986.0, 22.0)},
    )
    with pytest.raises(MissingEvents):
        ctx.kernel_roofline("glcm_haralick")
    assert ctx.kernel_roofline("pansharpen_rcs") is None
