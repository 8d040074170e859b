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
chip's idle time, and that the reduction reads such a trace as before.
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
    """The reduction reads only the benchmark's spans, so the program's
    spans inside them change no label and no reading."""
    t = xplane.reduce_file(SPANS_TRACE)
    assert t.program("jit_refill")[0] == 1 and t.program("jit_step")[0] == 2
    assert set(t.spans) == {"engine.step", "engine.submit"}
    idle = sum(s for _, s in t.gaps)
    assert idle == pytest.approx(t.window_s - t.busy_s, rel=1e-6)
    assert t.breakdown()["idle_gaps"][0][0] == (
        "engine.step, before jit_refill (x1)")
    run = _fake_run([_req(0, 3, (60, 60, 60))], trace=t, traced=(2, 3),
                    counters={"busy_slot_chunks": 8, "slot_chunks": 8})
    for name in PINNED:
        assert cells.reader(name).read(run) is not None
