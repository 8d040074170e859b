"""The continuous engine's own profiler spans and its staging counter.

A small engine (m = 12, 2 slots, 5 requests, so every slot is refilled
mid-flight) runs once untraced and once under `jax.profiler`; the trace
is read back with `ProfileData`, as the on-chip benchmark reads it
(`benchmarks/chip/xplane.py`).
"""
import glob
import math

import jax
import numpy as np
import pytest

from repro.core import (MSCConfig, PlantedSpec, make_msc_mesh,
                        make_planted_tensor)
from repro.serving import MSCContinuousEngine

M, SLOTS = 12, 2
GAMMAS = (40.0, 3.0, 60.0, 25.0, 90.0)


def _spans(path):
    """name -> [(start_ns, end_ns, stats)] of the program's host spans."""
    from jax.profiler import ProfileData

    found = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    assert len(found) == 1, found
    out = {}
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("msc."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return {k: sorted(v, key=lambda x: x[0]) for k, v in out.items()}


def _inside(span, parents):
    return any(p[0] <= span[0] and span[1] <= p[1] for p in parents)


def _summary(results):
    return [[(np.asarray(r[j].mask).tolist(), int(r[j].power_iters_run))
             for j in range(3)] for r in results]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(plain results, traced results, spans, stats delta, engine)."""
    mesh = make_msc_mesh("flat", devices=jax.devices()[:1], shape=(1, 1))
    cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2, power_iters=24,
                    power_check_every=6)
    eng = MSCContinuousEngine(mesh, cfg, slots=SLOTS)
    tensors = [np.asarray(make_planted_tensor(jax.random.PRNGKey(i),
                                              PlantedSpec.paper(M, g)))
               for i, g in enumerate(GAMMAS)]
    plain = eng.run(tensors)
    path = tmp_path_factory.mktemp("trace")
    base = eng.stats
    jax.profiler.start_trace(str(path))
    try:
        rids = [eng.submit(t) for t in tensors]
        got = {}
        while eng.has_work():
            got.update(eng.step())
    finally:
        jax.profiler.stop_trace()
    return (plain, [got[r] for r in rids], rids, _spans(path),
            eng.stats.delta(base), eng)


def test_tracing_leaves_masks_and_sweeps_unchanged(traced):
    plain, got = traced[0], traced[1]
    assert _summary(got) == _summary(plain)


def test_one_submit_span_per_request_with_its_rid(traced):
    rids, spans = traced[2], traced[3]
    assert [s[2] for s in spans["msc.submit"]] == [{"rid": r} for r in rids]


def test_one_admit_span_per_admission_inside_a_refill(traced):
    rids, spans = traced[2], traced[3]
    admits = spans["msc.admit"]
    assert sorted(s[2]["rid"] for s in admits) == sorted(rids)
    assert {s[2]["slot"] for s in admits} == set(range(SLOTS))
    for s in admits:
        assert set(s[2]) == {"rid", "slot"}
        assert _inside(s, spans["msc.refill"])
    # every admission of this run copies its tensor into the staging, and
    # re-zeroes its slot first: the untraced run left the staging dirty
    # and every tensor (m = 12) is smaller than its bucket
    bucket = traced[5].bucket_of((M, M, M))
    assert bucket != (M, M, M)
    for name in ("msc.admit.zero", "msc.admit.copy"):
        assert len(spans[name]) == len(admits)
        assert all(_inside(s, admits) for s in spans[name])
    for name in ("msc.refill.call", "msc.refill.read"):
        assert all(_inside(s, spans["msc.refill"]) for s in spans[name])


def test_dispatch_spans_count_the_dispatches(traced):
    spans, delta = traced[3], traced[4]
    assert len(spans["msc.refill"]) == delta.refills
    assert len(spans["msc.refill.call"]) == delta.refills
    assert len(spans["msc.chunk.call"]) == delta.chunk_steps
    assert len(spans["msc.chunk.read"]) == delta.chunk_steps
    # a refill reads results back only when it evicts
    assert 0 < len(spans["msc.refill.read"]) <= delta.refills
    for name in ("msc.refill", "msc.refill.call", "msc.chunk.call"):
        ticks = [s[2]["tick"] for s in spans[name]]
        assert ticks == sorted(ticks) and len(set(ticks)) == len(ticks)


def test_staged_bytes_are_the_host_arguments_of_the_refills(traced):
    """Every refill hands over its per-slot arrays (old and new dims and
    the resume sweep counts as (B, 3) int32, the resume flags as (B, 3)
    bool, the permutation as (B,) int32, take_new, new_done, use_warm and
    use_resume as (B,) bool); one that admits hands over the fp32 staging
    as well, one bucket-shaped cube per slot, and one that only evicts
    the device's zeros in its place."""
    spans, delta, eng = traced[3], traced[4], traced[5]
    bucket = eng.bucket_of((M, M, M))
    staging = SLOTS * math.prod(bucket) * 4
    small = SLOTS * (3 * 3 * 4 + 3 * 1 + 4 + 4 * 1)
    admitting = sum(1 for r in spans["msc.refill"]
                    if any(_inside(a, [r]) for a in spans["msc.admit"]))
    assert 0 < admitting < delta.refills
    assert delta.staged_bytes == (delta.refills * small
                                  + admitting * staging)
