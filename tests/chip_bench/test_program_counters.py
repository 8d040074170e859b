"""The benchmark's readers against the program's own instrumentation, on
the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chip_bench

The per-layer readers of `m400-lowgap-batch` are pinned on the recorded
trace `testdata/window.xplane.pb`, so a change to the reduction or to a
reader that moves a number shows here; the staging counter's reader is
checked on known counters; and `testdata/window_spans.xplane.pb`, a
refill cycle traced on one TPU v5 lite with the program's own spans
(`testdata/window_spans.md`), shows that those spans reach the trace
with their stats, nested as the engine opens them, that they cover the
chip's idle time, that the reduction splits that idle time by them, and
that the readers of the spans give what a hand count of them gives.
"""
from __future__ import annotations

import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(CHECKOUT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cells  # noqa: E402
import xplane  # noqa: E402
from test_chip_bench import _fake_run, _req  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "window.xplane.pb")
SPANS_TRACE = os.path.join(BENCH, "testdata", "window_spans.xplane.pb")

# what the readers give on the recorded window, three requests returned
# at ticks 3, 3 and 4 after ten chunks each, the window's ticks 2..3 traced
PINNED = {
    "device_idle_share.batch": 0.8727645268935194,
    "chunk_step_ms.batch": 66.18918200000013,
    "refill_ms.batch": 36.09704000000002,
    "eigensolve_roofline": 17.00088581355671,
    "engine_host_ms_per_tensor.batch": 577.7095294999997,
    "slot_occupancy.batch": 0.75,
}


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce_file(TRACE)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_readers_are_pinned_on_the_recorded_window(name, recorded):
    run = _fake_run([_req(0, 3, (60, 60, 60)), _req(1, 3, (60, 60, 60)),
                     _req(2, 4, (60, 60, 60))],
                    trace=recorded, traced=(2, 3),
                    counters={"busy_slot_chunks": 6, "slot_chunks": 8})
    assert cells.reader(name).read(run) == pytest.approx(PINNED[name],
                                                         rel=1e-12)


def test_the_reduction_is_pinned_on_the_recorded_window(recorded):
    assert recorded.window == pytest.approx((0.6955179020000001, 2.019389845),
                                            rel=1e-12)
    assert recorded.busy_s == pytest.approx(0.16844347300000084, rel=1e-12)
    assert len(recorded.busy) == 532
    assert {k: n for k, (n, _) in recorded.modules.items()} == {
        "jit_refill": 1, "jit_step": 2}
    assert recorded.modules["jit_step"][1] == pytest.approx(
        0.13237836400000025, rel=1e-12)
    assert {k: len(v) for k, v in recorded.spans.items()} == {
        "engine.step": 3, "engine.submit": 4}
    assert recorded.host_self_s() == pytest.approx(1.1554190589999993,
                                                   rel=1e-12)
    assert len(recorded.ops) == 485
    assert [label for label, _ in recorded.breakdown()["idle_gaps"]] == [
        "engine.step, before jit_refill (x1)",
        "engine.step, before jit_step (x2)",
        "engine.step, before the window's end (x1)",
        "inside jit_refill (x170)", "inside jit_step (x359)"]


@pytest.mark.parametrize("counters,want", [
    ({"staged_bytes": 3 * 3_072_000_188, "refills": 3}, 3072.000188),
    ({"staged_bytes": 0, "refills": 2}, 0.0),
    ({"staged_bytes": 5, "refills": 0}, None),
    # a program without the counter, as before it was added
    ({"refills": 4}, None),
])
def test_staged_megabytes_per_refill(counters, want):
    got = cells.reader("staged_mb_per_refill.batch").read(
        _fake_run([], counters=counters))
    assert got == (None if want is None else pytest.approx(want))


# ---- a recorded window with the program's spans ---------------------------

@pytest.fixture(scope="module")
def program_spans():
    """name -> [(start s, end s, stats)] of the msc.* host events."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(SPANS_TRACE).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("msc."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         dict(ev.stats)))
    return {k: sorted(v, key=lambda x: x[0]) for k, v in out.items()}


def _within(span, parent):
    return parent[0] <= span[0] and span[1] <= parent[1]


def test_the_programs_spans_reach_the_trace_nested(program_spans):
    (refill,) = program_spans["msc.refill"]
    admits = program_spans["msc.admit"]
    assert [a[2]["slot"] for a in admits] == [0, 1, 2, 3]
    assert len({a[2]["rid"] for a in admits}) == 4
    assert all(_within(a, refill) for a in admits)
    for part in ("msc.admit.zero", "msc.admit.unfold"):
        assert [sum(_within(x, a) for x in program_spans[part])
                for a in admits] == [1, 1, 1, 1]
    (call,) = program_spans["msc.refill.call"]
    (read,) = program_spans["msc.refill.read"]
    assert admits[-1][1] <= call[0] and call[1] <= read[0]
    assert _within(call, refill) and _within(read, refill)
    assert call[2] == read[2] == refill[2] == {"tick": refill[2]["tick"]}
    # the four requests returned by the refill are submitted again
    assert len(program_spans["msc.submit"]) == 4
    assert all(set(x[2]) == {"rid"} for x in program_spans["msc.submit"])
    for name in ("msc.chunk.call", "msc.chunk.read"):
        assert all(set(x[2]) == {"tick"} for x in program_spans[name])


def test_the_programs_spans_cover_the_idle_time(program_spans):
    """The device's refill starts while the host waits in msc.refill.read
    (the call returns before its arguments reach the chip), and all but
    a few milliseconds of the stretch before it, with the chip idle, lie
    inside the program's spans."""
    t = xplane.reduce_file(SPANS_TRACE)
    (call,) = program_spans["msc.refill.call"]
    (read,) = program_spans["msc.refill.read"]
    refill_start = min(s for s, e in t.busy if s >= call[0])
    assert read[0] < refill_start < read[1]
    edges = [(t.window[0],) * 2] + t.busy + [(t.window[1],) * 2]
    idle = [(e0, s1) for (_, e0), (s1, _) in zip(edges, edges[1:])
            if s1 - e0 > 0.1]
    assert len(idle) == 1 and idle[0][1] == refill_start
    covered = xplane.union(
        (max(s, t.window[0]), min(e, t.window[1]))
        for name in ("msc.admit", "msc.refill.call", "msc.refill.read")
        for s, e, _ in program_spans[name])
    gap = idle[0][1] - idle[0][0]
    assert xplane.overlap(covered, *idle[0]) >= 0.99 * gap


def test_a_trace_with_the_programs_spans_reduces_as_before():
    """The program's spans split the idle stretches between programs and
    change no other reading: the benchmark's spans, the programs and the
    idle seconds before each program are what they were without them."""
    t = xplane.reduce_file(SPANS_TRACE)
    assert t.program("jit_refill")[0] == 1 and t.program("jit_step")[0] == 2
    assert set(t.spans) == {"engine.step", "engine.submit"}
    idle = sum(s for _, s in t.gaps)
    assert idle == pytest.approx(t.window_s - t.busy_s, rel=1e-6)
    assert t.breakdown()["idle_gaps"][0][0] == (
        "msc.admit.unfold, before jit_refill (x4)")
    before = sum(s for label, s in t.gaps
                 if label.endswith(", before jit_refill"))
    # the one gap the reduction gave before the program's spans were read
    assert before == pytest.approx(1.156975878, rel=1e-9)
    run = _fake_run([_req(0, 3, (60, 60, 60))], trace=t, traced=(2, 3),
                    counters={"busy_slot_chunks": 8, "slot_chunks": 8})
    for name in PINNED:
        assert cells.reader(name).read(run) is not None


# ---- the program's spans in the reduction and their readers ---------------

# idle seconds by label and the number of stretches, as the reduction gave
# them on testdata/window.xplane.pb before it read the program's spans
NO_SPANS_GAPS = {
    "engine.step, before jit_refill": (1.143533169, 1),
    "inside jit_refill": (2.912000000065973e-05, 170),
    "engine.step, before jit_step": (0.01086513499999997, 2),
    "inside jit_step": (5.37999998773131e-07, 359),
    "engine.step, before the window's end": (0.0010005079999997335, 1),
}


def test_gap_labels_without_the_programs_spans_are_unchanged(recorded):
    assert recorded.events == {}
    got = {}
    for label, sec in recorded.gaps:
        s, n = got.get(label, (0.0, 0))
        got[label] = (s + sec, n + 1)
    assert set(got) == set(NO_SPANS_GAPS)
    for label, (sec, n) in NO_SPANS_GAPS.items():
        assert got[label][1] == n
        assert got[label][0] == pytest.approx(sec, rel=1e-9, abs=1e-15)


@pytest.fixture(scope="module")
def spans_reduced():
    return xplane.reduce_file(SPANS_TRACE)


def test_the_idle_gap_is_split_by_the_innermost_program_span(
        spans_reduced):
    """Hand sums of the spans in testdata/window_spans.xplane.pb that lie
    whole in the idle stretch before the refill: four admissions, each a
    re-zeroing and three transposes, then the call."""
    by = {}
    for label, sec in spans_reduced.gaps:
        s, n = by.get(label, (0.0, 0))
        by[label] = (s + sec, n + 1)
    want = {
        "msc.admit.unfold, before jit_refill":
            ((166071699 + 166116978 + 170241549 + 172543738) * 1e-9, 4),
        "msc.admit.zero, before jit_refill":
            ((33995989 + 34563880 + 36846269 + 35741059) * 1e-9, 4),
        "msc.refill.call, before jit_refill": (65511519 * 1e-9, 1),
    }
    for label, (sec, n) in want.items():
        assert by[label][1] == n
        assert by[label][0] == pytest.approx(sec, rel=1e-9)
    # the read waits until the chip starts the refill, 274.1 ms later
    sec, n = by["msc.refill.read, before jit_refill"]
    assert n == 1 and sec == pytest.approx(0.274124, rel=1e-5)
    # a part under no program span keeps the benchmark's label: the
    # engine's step between the last tick's read and this tick's refill
    assert by["engine.step, before jit_refill"][0] > 0


def test_the_reduction_keeps_the_programs_spans_with_their_stats(
        spans_reduced):
    t = spans_reduced
    assert [ev[2] for ev in t.events["msc.admit"]] == [
        {"rid": r, "slot": s} for r, s in ((9, 0), (10, 1), (11, 2),
                                           (12, 3))]
    assert [ev[2]["rid"] for ev in t.events["msc.submit"]] == [13, 14, 15,
                                                               16]
    assert [name for _, _, name in t.programs] == [
        "jit_refill", "jit_step", "jit_step"]


# hand values from the spans of testdata/window_spans.xplane.pb (ns): the
# four admissions last 200096498, 200719418, 207128028 and 208320237; the
# refill's call starts at 1560269087 and jit_refill at 1900018333; the four
# requests admitted were submitted before the recorded stretch, so no
# queue wait can be read there
SPANS_PINNED = {
    "admit_ms_per_tensor.batch":
        (200096498 + 200719418 + 207128028 + 208320237) / 4 * 1e-6,
    "refill_ship_ms.batch": (1900018333 - 1560269087) * 1e-6,
    "queue_wait_ms.batch": None,
}


@pytest.mark.parametrize("name", sorted(SPANS_PINNED))
def test_span_readers_on_the_recorded_spans(name, spans_reduced):
    run = _fake_run([], trace=spans_reduced, traced=(2, 3))
    got = cells.reader(name).read(run)
    want = SPANS_PINNED[name]
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))


def _span_trace():
    """A window of 4 s: submits of rids 1, 2 and 3; admissions of rid 0
    (submitted before the trace), 1, 2, and 1 again (a resume); refill
    calls at 1, 2 and 3 s with jit_refill on the chip at 1.2 and 3.5 s."""
    return xplane.Reduced(
        window=(0.0, 4.0), busy=[], busy_s=0.0, modules={}, ops={},
        spans={}, gaps=[],
        events={
            "msc.submit": [(1.0, 1.1, {"rid": 1}), (1.2, 1.3, {"rid": 2}),
                           (5.0, 5.1, {"rid": 3})],
            "msc.admit": [(0.5, 0.6, {"rid": 0, "slot": 0}),
                          (2.0, 2.1, {"rid": 1, "slot": 0}),
                          (2.1, 2.3, {"rid": 2, "slot": 1}),
                          (3.0, 3.3, {"rid": 1, "slot": 0}),
                          (4.5, 4.6, {"rid": 3, "slot": 1})],
            "msc.refill.call": [(1.0, 1.05, {"tick": 1}),
                                (2.0, 2.05, {"tick": 2}),
                                (3.0, 3.05, {"tick": 3})]},
        programs=[(1.2, 1.3, "jit_refill"), (2.2, 2.3, "jit_step"),
                  (3.5, 3.6, "jit_refill")])


@pytest.mark.parametrize("name,want", [
    # rid 1 waits 2.0 − 1.1 s and rid 2 2.1 − 1.3 s; rid 0 has no submit
    # in the trace, rid 3 is admitted after the window, rid 1's second
    # admission is a resume
    ("queue_wait_ms.batch", 850.0),
    # the four admissions that start in the window: 0.1, 0.1, 0.2, 0.3 s
    ("admit_ms_per_tensor.batch", 175.0),
    # call 1 s -> 1.2 s and call 3 s -> 3.5 s; call 2 s has no jit_refill
    # before the next call
    ("refill_ship_ms.batch", 350.0),
])
def test_span_readers_compute_known_values(name, want):
    got = cells.reader(name).read(_fake_run([], trace=_span_trace()))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SPANS_PINNED))
def test_span_readers_return_nothing_without_spans(name, recorded):
    assert cells.reader(name).read(_fake_run([])) is None
    assert cells.reader(name).read(_fake_run([], trace=recorded)) is None


def test_innermost_of_nested_spans():
    spans = [(0.0, 10.0, "a"), (1.0, 4.0, "b"), (2.0, 3.0, "c"),
             (5.0, 6.0, "d"), (12.0, 13.0, "e")]
    assert xplane.innermost(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"), (3.0, 4.0, "b"),
        (4.0, 5.0, "a"), (5.0, 6.0, "d"), (6.0, 10.0, "a"),
        (12.0, 13.0, "e")]
