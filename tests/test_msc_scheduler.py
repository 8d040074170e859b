"""SLO-aware continuous scheduler (DESIGN.md §7.12).

Coverage layers:
  * the queue-wait model: `roofline.expected_queue_wait` closed form and
    the arrival/priority extension of `continuous_serving_model`
    (per-class p50/p99 + shed prediction).
  * per-class queue mechanics on a bare `_SlotTable`: weighted-aging
    `pop_best` (urgent-first, aging overtake, FIFO within class,
    urgent-wins-ties) and the per-class per-bucket starvation bound.
  * engine policy units: submit validation, SLO load-shedding before
    solving, deadline-miss accounting, idle_bucket_ticks == 0 at
    refill_min_free == 1, cross-bucket weighted rotation parity.
  * preempt-to-host: a forced preempt→resume interleaving delivers
    masks and realized sweep counts bit-identical to the sequential
    oracle, performs ZERO new traces/compiles on a warm bucket
    (jax.monitoring), saves identical `warm_sweeps_saved` for a
    warm-started victim (no double-seeding), and round-trips parked
    state through an engine checkpoint.
  * the scheduling property (hypothesis, subprocess meshes): ANY
    arrival order × priority mix × preemption schedule produces
    oracle-identical masks and per-request `power_iters_run` on (8,1)
    and (4,2) meshes.
"""
import numpy as np
import pytest

import jax

from repro.roofline import continuous_serving_model, expected_queue_wait

# Near-noise γ=2 requests run toward the sweep cap while γ≥90 requests
# gate in a chunk or two — the bimodal mix the preemption policy's
# conditional-tail predictor is built for.  Seeding the histogram with
# cap-runners makes every resident slot predict a long remaining tail,
# so a strictly-more-urgent waiter deterministically triggers preempt.
FORCED_TAIL = (60, 60, 54, 48)


def _warm_hist(eng):
    eng._sweep_hist.extend(FORCED_TAIL)


# ------------------------------------------------ queue-wait model ----

class TestQueueWaitModel:
    def test_free_slots_cover_the_queue(self):
        assert expected_queue_wait(0, 1, 8, 4.0) == 0.0
        assert expected_queue_wait(2, 3, 8, 4.0) == 0.0

    def test_backlog_drains_at_table_rate(self):
        # position 3 behind 0 free slots: ceil-free + 1 = 4 turnovers
        # at B=2 slots freeing once per 6 chunks
        assert expected_queue_wait(3, 0, 2, 6.0) == pytest.approx(12.0)

    def test_more_free_slots_never_hurts(self):
        w = [expected_queue_wait(5, f, 4, 4.0) for f in range(5)]
        assert w == sorted(w, reverse=True)

    def test_rejects_empty_table(self):
        with pytest.raises(ValueError, match="B"):
            expected_queue_wait(1, 0, 0, 4.0)

    def test_model_reports_per_class_waits(self):
        hist = ([60] + [12] * 7) * 4
        r = continuous_serving_model(
            hist, 4, arrivals=[i // 2 for i in range(len(hist))],
            priorities=[i % 2 for i in range(len(hist))])
        assert set(r["wait_by_class"]) == {0, 1}
        for cls in (0, 1):
            w = r["wait_by_class"][cls]
            assert 0.0 <= w["p50"] <= w["p99"], w
        assert r["wait_p99_chunks"] >= r["wait_p50_chunks"]
        assert r["shed"] == 0

    def test_urgent_class_waits_less_under_load(self):
        hist = [60] * 8 + [12] * 24
        r = continuous_serving_model(
            hist, 2, arrivals=[i // 4 for i in range(len(hist))],
            priorities=[0 if i % 4 == 0 else 1 for i in range(len(hist))])
        assert (r["wait_by_class"][0]["p99"]
                <= r["wait_by_class"][1]["p99"]), r["wait_by_class"]

    def test_slo_bound_sheds_in_the_model(self):
        hist = [60] * 16
        dense = [0] * 16           # everyone arrives at once: overload
        kept = continuous_serving_model(hist, 2, arrivals=dense)
        shed = continuous_serving_model(hist, 2, arrivals=dense,
                                        slo_chunks=1)
        assert kept["shed"] == 0
        assert shed["shed"] > 0
        assert shed["wait_p99_chunks"] <= kept["wait_p99_chunks"]


# -------------------------------------------- per-class queue units ---

def _bare_table(eng, slots=4):
    from repro.serving.msc_engine import _SlotTable

    return _SlotTable((16, 16, 16), None, None, slots, np.float32,
                      eng._plan.mode_shapes((16, 16, 16), slots))


class TestClassQueues:
    def _engine(self, **kw):
        from repro.core import MSCConfig, make_msc_mesh
        from repro.serving import MSCContinuousEngine

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        return MSCContinuousEngine(mesh,
                                   MSCConfig(epsilon=3e-4, power_tol=1e-2),
                                   **kw)

    def test_pop_best_urgent_class_first(self):
        tb = _bare_table(self._engine())
        tb.queue_for(1).append((11, 0, -1))
        tb.queue_for(0).append((22, 10, -1))
        # tick 12, aging 16: eff(0) = -2/16 beats eff(1) = 1 - 12/16
        assert tb.pop_best(12, 16)[1] == 22
        assert tb.pop_best(12, 16)[1] == 11
        assert tb.pop_best(12, 16) is None

    def test_pop_best_aging_overtake(self):
        # a class-1 request that has waited > aging_chunks ticks beats a
        # fresh class-0 arrival: eff(1) = 1 - 30/16 < eff(0) = -2/16
        tb = _bare_table(self._engine())
        tb.queue_for(1).append((11, 0, -1))
        tb.queue_for(0).append((22, 28, -1))
        assert tb.pop_best(30, 16)[1] == 11

    def test_pop_best_exact_tie_goes_urgent(self):
        # submitted exactly aging_chunks apart ⇒ equal eff at every
        # tick; the more urgent class must win the tie
        tb = _bare_table(self._engine())
        tb.queue_for(1).append((11, 0, -1))
        tb.queue_for(0).append((22, 16, -1))
        assert tb.pop_best(40, 16)[1] == 22

    def test_pop_best_fifo_within_class(self):
        tb = _bare_table(self._engine())
        tb.queue_for(0).append((1, 0, -1))
        tb.queue_for(0).append((2, 0, -1))
        assert tb.pop_best(5, 16)[1] == 1
        assert tb.pop_best(5, 16)[1] == 2

    def test_queued_lists_classes_ascending(self):
        tb = _bare_table(self._engine())
        tb.queue_for(2).append((5, 0, -1))
        tb.queue_for(0).append((6, 1, 9))
        assert [e[:2] for e in tb.queued()] == [(0, 6), (2, 5)]
        assert tb.queue_len() == 2

    def test_starvation_bound_is_per_class(self):
        """A single aged CLASS trips the bound even when other classes
        are fresh and free slots are below refill_min_free."""
        eng = self._engine(slots=4, refill_min_free=4, max_queue_chunks=4)
        tb = _bare_table(eng)
        tb.slot_req = [1, 2, 3, None]
        eng._tick = 10
        tb.queue_for(0).append((7, 9, -1))      # waited 1 tick: no
        assert not eng._should_admit(tb, 1)
        tb.queue_for(3).append((8, 6, -1))      # class 3 waited 4: yes
        assert eng._should_admit(tb, 1)

    def test_starvation_bound_admits_low_class_despite_batching(self):
        """Regression (§7.12 satellite): refill_min_free == slots would
        defer admission until a full drain; the per-class bound plus
        weighted aging still get a lone class-1 request served from
        behind a class-0 stream."""
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=2, refill_min_free=2, max_queue_chunks=2,
                           aging_chunks=4)
        ts = [make_planted_tensor(jax.random.PRNGKey(i),
                                  PlantedSpec.paper(14, g))
              for i, g in enumerate((30.0, 70.0, 90.0, 40.0, 60.0))]
        outs = eng.run(ts, priorities=[1, 0, 0, 0, 0])
        assert all(o is not None for o in outs)
        assert eng.stats.evictions == 5
        assert eng.stats.requests == 5


# ------------------------------------------------ engine policy -------

class TestSchedulerPolicy:
    def _engine(self, **kw):
        from repro.core import MSCConfig, make_msc_mesh
        from repro.serving import MSCContinuousEngine

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        return MSCContinuousEngine(mesh,
                                   MSCConfig(epsilon=3e-4, power_tol=1e-2),
                                   **kw)

    def test_rejects_bad_priority(self):
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine()
        t = make_planted_tensor(jax.random.PRNGKey(0),
                                PlantedSpec.paper(14, 70.0))
        with pytest.raises(ValueError, match="priority"):
            eng.submit(t, priority=-1)
        with pytest.raises(ValueError, match="deadline_chunks"):
            eng.submit(t, deadline_chunks=0)

    def test_rejects_bad_bucket_policy(self):
        with pytest.raises(ValueError, match="bucket_policy"):
            self._engine(bucket_policy="round-robin")

    def test_slo_shed_before_solving(self):
        """With slo_chunks=0 and a single slot, the second submit's
        predicted wait exceeds the bound → LoadShedError BEFORE any
        device work; the admitted request still drains."""
        from repro.core import PlantedSpec, make_planted_tensor
        from repro.serving import LoadShedError

        eng = self._engine(slots=1, slo_chunks=0)
        ts = [make_planted_tensor(jax.random.PRNGKey(i),
                                  PlantedSpec.paper(14, 70.0))
              for i in range(2)]
        rid = eng.submit(ts[0])
        with pytest.raises(LoadShedError, match="SLO"):
            eng.submit(ts[1])
        s = eng.stats
        assert s.slo_sheds == 1 and s.shed_requests == 1
        assert s.dispatches == 0  # shed before solving anything
        got = {}
        while eng.has_work():
            got.update(eng.step())
        assert rid in got

    def test_deadline_miss_is_counted_and_advisory(self):
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=1)
        t = make_planted_tensor(jax.random.PRNGKey(0),
                                PlantedSpec.paper(14, 70.0))
        rid = eng.submit(t, deadline_chunks=1)  # admission alone eats it
        got = {}
        while eng.has_work():
            got.update(eng.step())
        assert got[rid] is not None          # advisory: still delivered
        assert eng.stats.deadline_misses == 1

    def test_generous_deadline_not_missed(self):
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=1)
        t = make_planted_tensor(jax.random.PRNGKey(0),
                                PlantedSpec.paper(14, 90.0))
        eng.run([t], deadline_chunks=[512])
        assert eng.stats.deadline_misses == 0

    def test_no_idle_ticks_at_min_free_one(self):
        """refill_min_free == 1 admits at every free slot — the bench's
        idle_bucket_ticks == 0 bar, by construction."""
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=2)  # default refill_min_free=1
        ts = [make_planted_tensor(jax.random.PRNGKey(i),
                                  PlantedSpec.paper(14, g))
              for i, g in enumerate((30.0, 70.0, 90.0, 40.0))]
        eng.run(ts)
        assert eng.stats.idle_bucket_ticks == 0

    def test_refill_batching_counts_idle_ticks(self):
        """A half-empty table chunk-stepping past a non-empty queue
        (refill_min_free deferral) is exactly what the counter bills."""
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=2, refill_min_free=2, max_queue_chunks=64,
                           preempt=False)
        slow = make_planted_tensor(jax.random.PRNGKey(0),
                                   PlantedSpec.paper(14, 2.0))
        fast = make_planted_tensor(jax.random.PRNGKey(1),
                                   PlantedSpec.paper(14, 90.0))
        eng.submit(slow)
        eng.step()                 # admits into the 2-free table
        eng.submit(fast)           # queues: 1 free < refill_min_free
        got = {}
        while eng.has_work():
            got.update(eng.step())
        assert len(got) == 2
        assert eng.stats.idle_bucket_ticks > 0

    def test_weighted_rotation_matches_all_policy(self):
        """Cross-bucket device-time sharing is results-neutral: the
        weighted rotation serves a two-bucket mix with per-request
        masks and sweep counts identical to stepping every bucket."""
        from repro.core import PlantedSpec, make_planted_tensor

        sizes = (14, 21, 15, 22, 16)
        ts = [make_planted_tensor(jax.random.PRNGKey(i),
                                  PlantedSpec.paper(mm, 70.0))
              for i, mm in enumerate(sizes)]
        outs = {}
        for policy in ("weighted", "all"):
            eng = self._engine(slots=2, bucket_policy=policy)
            assert len({eng.bucket_of(t.shape) for t in ts}) == 2
            outs[policy] = eng.run(ts)
        for a, b in zip(outs["weighted"], outs["all"]):
            for j in range(3):
                assert (a[j].mask == b[j].mask).all()
                assert int(a[j].power_iters_run) == \
                    int(b[j].power_iters_run)

    def test_multiprocess_mesh_parks_preemption(self):
        eng = self._engine(replicate_outputs=True, preempt=True)
        assert eng.preempt is False


# -------------------------------------------- preempt-to-host ---------

def _preempt_setup(tmpdir=None, **kw):
    """Two near-noise class-1 residents on a 2-slot table, a seeded
    cap-runner histogram, then fast class-0 arrivals — the deterministic
    preempt→resume interleaving."""
    from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                            make_msc_mesh)
    from repro.serving import MSCContinuousEngine

    mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
    cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2)
    specs = [PlantedSpec.paper(14, 2.0), PlantedSpec.paper(14, 2.0),
             PlantedSpec.paper(14, 150.0), PlantedSpec.paper(14, 150.0)]
    tensors = [make_planted_tensor(jax.random.PRNGKey(40 + i), s)
               for i, s in enumerate(specs)]
    eng = MSCContinuousEngine(mesh, cfg, slots=2,
                              preempt_min_remaining_chunks=1,
                              checkpoint_dir=tmpdir, ckpt_every_chunks=0,
                              **kw)
    return eng, cfg, tensors


def _drive_preemption(eng, tensors):
    """Submit slow class-1 pair, let them occupy both slots, then race
    fast class-0 pair against them.  Returns rid → input index."""
    rids = {eng.submit(tensors[i], priority=1): i for i in range(2)}
    got = {}
    for _ in range(3):           # admit + a couple of chunks
        got.update(eng.step())
    _warm_hist(eng)
    rids.update({eng.submit(tensors[i], priority=0): i for i in (2, 3)})
    return rids, got


class TestPreemptToHost:
    def test_preempt_resume_bit_exact(self):
        """Masks AND realized sweep counts through a forced
        preempt→resume interleaving equal the unpadded sequential
        oracle — the §7.12 correctness bar."""
        from repro.core import msc_sequential

        eng, cfg, tensors = _preempt_setup()
        refs = [msc_sequential(t, cfg) for t in tensors]
        rids, got = _drive_preemption(eng, tensors)
        while eng.has_work():
            got.update(eng.step())
        s = eng.stats
        assert s.preemptions >= 1, s
        assert s.resumes == s.preemptions, s
        for rid, i in rids.items():
            res, ref = got[rid], refs[i]
            for j in range(3):
                assert (res[j].mask == np.asarray(ref[j].mask)).all(), (i, j)
                assert int(res[j].power_iters_run) == \
                    int(ref[j].power_iters_run), (i, j)
        # a request is admitted one tick after its submit at the
        # earliest, and a preempted one waits again before it resumes
        assert s.queue_wait_chunks / s.requests >= 1.0

    def test_preempting_stream_zero_warm_recompiles(self):
        """The resume inputs are part of the ONE lowered refill
        signature: a warm bucket preempts and resumes with no traces
        and no compiles (jax.monitoring + engine counters)."""
        import jax.monitoring as mon

        eng, _, tensors = _preempt_setup()
        eng.run(tensors[2:])                # warm both executables
        assert eng.stats.compiles == 2
        events = []
        mon.register_event_duration_secs_listener(
            lambda ev, dur, **kw: events.append(ev)
            if "compile" in ev or "trace" in ev else None)
        try:
            before = eng.stats
            rids, got = _drive_preemption(eng, tensors)
            while eng.has_work():
                got.update(eng.step())
            delta = eng.stats.delta(before)
        finally:
            mon.clear_event_listeners()
        assert delta.preemptions >= 1 and delta.resumes >= 1, delta
        assert events == [], f"preempting stream traced/compiled: {events}"
        assert delta.compiles == 0, delta
        assert sorted(got) >= sorted(rids)

    def test_preempted_warm_start_saves_same_sweeps(self):
        """A tier-2 warm-started request preempted mid-solve reports the
        SAME warm_sweeps_saved as an uninterrupted run: the resume path
        must not re-seed the carry (double-seeding) nor re-capture a
        stale sketch for the cache."""
        from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                                make_msc_mesh)
        from repro.serving import MSCContinuousEngine, MSCResultCache

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2)
        donor = np.asarray(make_planted_tensor(jax.random.PRNGKey(7),
                                               PlantedSpec.paper(14, 2.0)),
                           np.float32)
        # a perturbation big enough that the warm start does NOT gate at
        # its first probe (the victim must still be resident when the
        # urgent request lands) yet within the widened sketch tolerance
        rng = np.random.RandomState(3)
        near = donor + 0.2 * donor.std() * rng.standard_normal(
            donor.shape).astype(np.float32)
        fast = make_planted_tensor(jax.random.PRNGKey(8),
                                   PlantedSpec.paper(14, 150.0))

        def serve(interfere):
            cache = MSCResultCache(max_bytes=64 << 20, sketch_tol=0.6)
            eng = MSCContinuousEngine(mesh, cfg, slots=1,
                                      result_cache=cache, warm_start=True,
                                      preempt_min_remaining_chunks=1)
            eng.run([donor])               # seed the cache (tier 2 donor)
            base = eng.stats
            rid = eng.submit(near, priority=1)
            got = eng.step()               # admit the warm-started slot
            if interfere:
                _warm_hist(eng)
                eng.submit(fast, priority=0)   # forces preempt at slots=1
            while eng.has_work():
                got.update(eng.step())
            d = eng.stats.delta(base)
            assert d.warm_starts == 1, d
            return got[rid], d

        res_a, d_a = serve(interfere=False)
        res_b, d_b = serve(interfere=True)
        assert d_b.preemptions >= 1 and d_b.resumes >= 1, d_b
        assert d_a.warm_sweeps_saved == d_b.warm_sweeps_saved, (d_a, d_b)
        assert d_a.warm_sweeps_saved > 0, d_a
        for j in range(3):
            assert (res_a[j].mask == res_b[j].mask).all(), j
            assert int(res_a[j].power_iters_run) == \
                int(res_b[j].power_iters_run), j

    def test_parked_state_survives_checkpoint(self, tmp_path):
        """Checkpoint taken WHILE a request is parked on host restores
        it — queues, parked carries, and the scheduler clock — and the
        drained results still match the sequential oracle."""
        from repro.core import msc_sequential
        from repro.serving import MSCContinuousEngine

        eng, cfg, tensors = _preempt_setup(tmpdir=str(tmp_path))
        refs = [msc_sequential(t, cfg) for t in tensors]
        rids, got = _drive_preemption(eng, tensors)
        for _ in range(64):
            if any(tb.parked for tb in eng._tables.values()):
                break
            got.update(eng.step())
        else:
            pytest.fail("preemption never parked a request")
        assert eng.checkpoint() is not None
        eng2 = MSCContinuousEngine.restore(str(tmp_path))
        assert any(tb.parked for tb in eng2._tables.values())
        while eng2.has_work():
            got.update(eng2.step())
        assert eng2.stats.resumes >= 1
        for rid, i in rids.items():
            res, ref = got[rid], refs[i]
            for j in range(3):
                assert (res[j].mask == np.asarray(ref[j].mask)).all(), (i, j)
                assert int(res[j].power_iters_run) == \
                    int(ref[j].power_iters_run), (i, j)


# ------------------------------------ scheduling property (meshes) ----

# The example loop runs INSIDE the subprocess: one mesh spin-up
# amortizes all examples, and the engine's executables stay warm across
# them.  The property is the §7.12 correctness bar verbatim: any
# arrival order × priority mix × preemption schedule yields
# oracle-identical masks and per-request realized sweep counts.
# hypothesis drives the draws when installed; otherwise seeded random
# draws cover the same space (the repo's test extra is optional, and
# the property must not go dark without it).
SCHED_PROPERTY = r"""
import numpy as np, jax
from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        msc_sequential, make_msc_mesh)
from repro.serving import MSCContinuousEngine
p, q = {p}, {q}
mesh = make_msc_mesh("flat", devices=jax.devices()[:p * q], shape=(p, q))
cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2)
specs = [PlantedSpec.paper(14, 2.0), PlantedSpec.paper(14, 150.0),
         PlantedSpec.paper(21, 150.0), PlantedSpec.paper(21, 2.0),
         PlantedSpec.paper(14, 90.0)]
tensors = [make_planted_tensor(jax.random.PRNGKey(i), s)
           for i, s in enumerate(specs)]
refs = [msc_sequential(t, cfg) for t in tensors]
eng = MSCContinuousEngine(mesh, cfg, slots=2,
                          preempt_min_remaining_chunks=1)
eng._sweep_hist.extend((60, 60, 54, 48))
n = len(tensors)

def check(order, prios, preempt):
    eng.preempt = preempt
    rids = {{}}
    for k, i in enumerate(order):
        rids[eng.submit(tensors[i], priority=int(prios[k]),
                        deadline_chunks=96)] = i
    got = {{}}
    while eng.has_work():
        got.update(eng.step())
    for rid, i in rids.items():
        res, ref = got[rid], refs[i]
        for j in range(3):
            assert (res[j].mask == np.asarray(ref[j].mask)).all(), \
                (order, prios, preempt, i, j)
            assert int(res[j].power_iters_run) == \
                int(ref[j].power_iters_run), (order, prios, preempt, i, j)

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    run = settings(max_examples=3, deadline=None, derandomize=True,
                   suppress_health_check=list(HealthCheck))(
        given(order=st.permutations(list(range(n))),
              prios=st.lists(st.integers(0, 2), min_size=n, max_size=n),
              preempt=st.booleans())(check))
    run()
    mode = "hypothesis"
except ImportError:
    rng = np.random.RandomState(0)
    for ex in range(3):
        check(list(rng.permutation(n)), rng.randint(0, 3, size=n),
              preempt=(ex != 1))
    mode = "seeded"
assert eng.stats.compiles == 4  # 16^3 and 24^3 buckets, 2 execs each
print("OK", mode, "preemptions=", eng.stats.preemptions)
"""


@pytest.mark.parametrize("p,q", [(8, 1), (4, 2)])
def test_scheduling_property_oracle_identical(subproc, p, q):
    out = subproc(SCHED_PROPERTY.format(p=p, q=q), p * q, timeout=900)
    assert "OK" in out
