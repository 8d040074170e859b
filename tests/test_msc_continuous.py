"""Continuous-batching MSC engine (DESIGN.md §7.7).

Coverage layers:
  * the serving determinism contract: per-request masks, d, and
    realized sweep counts through `MSCContinuousEngine` are invariant
    under request arrival order, slot placement policy, and
    eviction/refill batching — and equal to the unpadded sequential
    oracle — on (8,1) and (4,2) meshes × both epilogues (subprocess
    shard_map tests, like tests/test_msc_serving.py).  The stream is
    longer than the slot table, so every run exercises mid-flight
    eviction + refill.
  * the resumable-solver refactor: host-driven `step_chunk` over a
    persistent SolveState reproduces the in-jit `_gated_loop`
    bit-exactly (same iterates, same realized sweeps), for the einsum
    and Pallas-kernel chunk bodies.
  * the two-executable cache contract: a warm bucket performs zero
    traces/compiles across chunk-step AND refill dispatches, pinned by
    jax.monitoring and the engine's counters.
  * the batched collective relayout satellite:
    `build_msc_batched(relayout="collective")` parity vs the gspmd path
    at B ∈ {2, 8}.
  * engine scheduler units (starvation bound, placement permutations,
    stats accounting) and the roofline continuous_serving_model.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import continuous_serving_model

# Queue (6 requests) > slots (2) forces mid-flight eviction/refill; the
# gamma spread makes convergence skewed so evictions interleave; the
# non-cube request exercises bucket padding through the slot table.
CONTINUOUS_PARITY = r"""
import numpy as np, jax
from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        msc_sequential, make_msc_mesh)
from repro.serving import MSCContinuousEngine
p, q = {p}, {q}
mesh = make_msc_mesh("flat", devices=jax.devices()[:p * q], shape=(p, q))
specs = [PlantedSpec.paper(21, 70.0),
         PlantedSpec.paper(23, 30.0),
         PlantedSpec(shape=(18, 23, 15), cluster_sizes=(2, 3, 2),
                     gamma=60.0),
         PlantedSpec.paper(17, 90.0),
         PlantedSpec.paper(24, 40.0),
         PlantedSpec.paper(22, 35.0)]
tensors = [make_planted_tensor(jax.random.PRNGKey(i), s)
           for i, s in enumerate(specs)]
orders = [list(range(6)), [5, 4, 3, 2, 1, 0], [2, 0, 5, 1, 4, 3]]
for epilogue, rtol in (("allgather", 3e-5), ("ring", 3e-5)):
    cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2, epilogue=epilogue)
    refs = [msc_sequential(t, cfg) for t in tensors]
    eng = MSCContinuousEngine(mesh, cfg, slots=2)
    for order, placement, rmf in zip(orders,
                                     ("compact", "stable", "compact"),
                                     (1, 1, 2)):
        eng.placement, eng.refill_min_free = placement, rmf
        perm_res = eng.run([tensors[i] for i in order])
        for pos, i in enumerate(order):
            res, ref, t = perm_res[pos], refs[i], tensors[i]
            for j in range(3):
                assert res[j].mask.shape == (t.shape[j],), res[j].mask.shape
                assert (res[j].mask == np.asarray(ref[j].mask)).all(), \
                    (epilogue, order, t.shape, j)
                np.testing.assert_allclose(res[j].d, np.asarray(ref[j].d),
                                           rtol=rtol, atol=rtol)
                assert int(res[j].power_iters_run) == \
                    int(ref[j].power_iters_run), (epilogue, order, i, j)
    assert eng.stats.evictions == 18, eng.stats  # 6 requests x 3 runs
print("OK")
"""

COLLECTIVE_PARITY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        make_msc_mesh)
from repro.core.parallel import build_msc_batched
p, q = {p}, {q}
mesh = make_msc_mesh("flat", devices=jax.devices()[:p * q], shape=(p, q))
cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2)
shapes = [(14, 23, 15), (16, 24, 16), (10, 17, 12), (13, 21, 9)]
for B in (2, 8):
    bucket = (16, 24, 16)
    batch = np.zeros((B,) + bucket, np.float32)
    dims = np.ones((B, 3), np.int32)
    for i in range(B):
        sh = shapes[i % len(shapes)]
        t = np.asarray(make_planted_tensor(
            jax.random.PRNGKey(i),
            PlantedSpec(shape=sh, cluster_sizes=(2, 3, 2), gamma=60.0)))
        batch[i, :sh[0], :sh[1], :sh[2]] = t
        dims[i] = sh
    g = build_msc_batched(mesh, cfg)(jnp.asarray(batch), jnp.asarray(dims))
    c = build_msc_batched(mesh, cfg, relayout="collective")(
        jnp.asarray(batch), jnp.asarray(dims))
    for j in range(3):
        assert (np.asarray(g.modes[j].mask) ==
                np.asarray(c.modes[j].mask)).all(), (B, j)
        np.testing.assert_allclose(np.asarray(g.modes[j].d),
                                   np.asarray(c.modes[j].d),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_array_equal(
            np.asarray(g.modes[j].power_iters_run),
            np.asarray(c.modes[j].power_iters_run))
print("OK")
"""

# The refill unfolds the staged cubes on the device: its blocks must be
# byte-identical to the restore path's host transposes (rebuild_blocks),
# whether the tensor fills the bucket (no re-zeroing of the dirty slot)
# or is smaller than it (re-zeroed, so the padding reads zero).
STAGING_UNFOLD = r"""
import numpy as np, jax
import repro.serving.msc_engine as me
from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                        make_msc_mesh)
from repro.serving import MSCContinuousEngine
p, q = {p}, {q}
mesh = make_msc_mesh("flat", devices=jax.devices()[:p * q], shape=(p, q))
spans = []
traced = me.TraceAnnotation
def counted(name, **kw):
    spans.append(name)
    return traced(name, **kw)
me.TraceAnnotation = counted
cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2, power_iters=12,
                power_check_every=6)
eng = MSCContinuousEngine(mesh, cfg, slots=2)
bucket = eng.bucket_of((16, 16, 16))
assert bucket == (16, 16, 16), bucket
small = PlantedSpec(shape=(13, 16, 11), cluster_sizes=(2, 3, 2), gamma=60.0)
full = PlantedSpec.paper(16, 70.0)
slots, zeros = [], []
for i, spec in enumerate((small, full, small)):
    t = np.asarray(make_planted_tensor(jax.random.PRNGKey(i), spec))
    before = len(spans)
    rid = eng.submit(t)
    got = eng.step()
    tb = eng._tables[bucket]
    s = tb.slot_req.index(rid)
    want = eng._plan_for(bucket).rebuild_blocks(bucket, eng.slots,
                                                eng.dtype, tb.arrs)
    for j in range(3):
        blk, ref = np.asarray(tb.blocks[j]), np.asarray(want[j])
        assert blk.shape == ref.shape, (i, j, blk.shape, ref.shape)
        assert blk.tobytes() == ref.tobytes(), (i, j)
    pad = np.asarray(tb.blocks[0])[s]
    assert not pad[t.shape[0]:].any() and not pad[:, t.shape[1]:].any()
    assert not pad[:, :, t.shape[2]:].any()
    assert spans[before:].count("msc.admit.copy") == 1
    slots.append(s)
    zeros.append(spans[before:].count("msc.admit.zero"))
    while rid not in got:
        got.update(eng.step())
# one slot, dirty from the second admission on: the full cube skips the
# re-zeroing, the smaller tensor after it re-zeroes
assert slots == [slots[0]] * 3, slots
assert zeros == [0, 0, 1], zeros
print("OK")
"""


@pytest.mark.parametrize("p,q", [(1, 1), (8, 1), (2, 4)])
def test_refill_unfolds_staging_like_rebuild_blocks(subproc, p, q):
    out = subproc(STAGING_UNFOLD.format(p=p, q=q), p * q, timeout=600)
    assert "OK" in out


@pytest.mark.parametrize("p,q", [(8, 1), (4, 2)])
def test_continuous_matches_sequential_under_interleavings(subproc, p, q):
    out = subproc(CONTINUOUS_PARITY.format(p=p, q=q), p * q, timeout=900)
    assert "OK" in out


@pytest.mark.parametrize("p,q", [(8, 1), (4, 2)])
def test_batched_collective_relayout_matches_gspmd(subproc, p, q):
    out = subproc(COLLECTIVE_PARITY.format(p=p, q=q), p * q, timeout=900)
    assert "OK" in out


def test_batched_collective_rejects_unknown_relayout():
    from repro.core import MSCConfig, make_msc_mesh
    from repro.core.parallel import build_msc_batched

    mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="relayout"):
        build_msc_batched(mesh, MSCConfig(), relayout="nope")


# ------------------------------------------- resumable solver layer --

class TestStepChunk:
    """Host-driven step_chunk == in-jit _gated_loop, bit for bit."""

    def _drive(self, slices, cfg, chunk_builder):
        from repro.core.power_iter import (_init_vectors, init_solve_state,
                                           step_chunk)

        chunk_fn, k = chunk_builder(slices, cfg)
        state = init_solve_state(
            _init_vectors(slices.shape[:-2], slices.shape[-1]))
        stepper = jax.jit(lambda s: step_chunk(
            chunk_fn, s, k=k, n_iters=cfg.power_iters, tol=cfg.power_tol))
        for _ in range(cfg.power_iters // k + 1):
            state = stepper(state)
        return state

    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_host_driven_equals_gated_loop(self, use_kernels):
        from repro.core import MSCConfig
        from repro.core.power_iter import build_chunk_fn, top_eigenpairs

        cfg = MSCConfig(power_tol=1e-2, power_iters=24, power_check_every=6,
                        use_kernels=use_kernels)
        slices = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 13, 7))
        state = self._drive(slices, cfg,
                            lambda s, c: build_chunk_fn(s, c))
        lam, v, iters = top_eigenpairs(slices, cfg)
        np.testing.assert_array_equal(np.asarray(state.v), np.asarray(v))
        np.testing.assert_array_equal(np.asarray(state.iters),
                                      np.asarray(iters))

    def test_finished_state_is_frozen(self):
        from repro.core import MSCConfig
        from repro.core.power_iter import build_chunk_fn

        cfg = MSCConfig(power_tol=1e-1, power_iters=60, power_check_every=6)
        # strongly separated -> gate fires fast, then state must freeze
        slices = jnp.stack([jnp.outer(jnp.ones(11), jnp.ones(6)) * 9.0
                            + 0.01 * jax.random.normal(
                                jax.random.PRNGKey(1), (11, 6))])
        state = self._drive(slices, cfg, lambda s, c: build_chunk_fn(s, c))
        assert bool(state.done.all())
        v0, it0 = np.asarray(state.v), np.asarray(state.iters)
        from repro.core.power_iter import step_chunk
        chunk_fn, k = build_chunk_fn(slices, cfg)
        again = step_chunk(chunk_fn, state, k=k, n_iters=cfg.power_iters,
                           tol=cfg.power_tol)
        np.testing.assert_array_equal(np.asarray(again.v), v0)
        np.testing.assert_array_equal(np.asarray(again.iters), it0)

    def test_exhausted_includes_cap(self):
        from repro.core.power_iter import SolveState

        st = SolveState(v=jnp.zeros((2, 3, 4)), lam=jnp.zeros((2, 3)),
                        resid=jnp.zeros((2, 3)),
                        iters=jnp.array([12, 6], jnp.int32),
                        done=jnp.array([False, False]))
        np.testing.assert_array_equal(np.asarray(st.exhausted(12)),
                                      [True, False])

    def test_gram_path_rejected(self):
        from repro.core import MSCConfig
        from repro.core.power_iter import build_chunk_fn

        with pytest.raises(ValueError, match="matrix_free"):
            build_chunk_fn(jnp.zeros((2, 3, 4)),
                           MSCConfig(matrix_free=False))


# ------------------------------------------ executable-cache contract --

def test_warm_bucket_zero_recompiles_both_executables():
    """Across a whole warm stream — chunk-step AND refill dispatches —
    no traces, no compiles: jax.monitoring + engine counters."""
    import jax.monitoring as mon

    from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                            make_msc_mesh)
    from repro.serving import MSCContinuousEngine

    mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
    eng = MSCContinuousEngine(mesh, MSCConfig(epsilon=3e-4, power_tol=1e-2),
                              slots=2)
    tensors = [make_planted_tensor(jax.random.PRNGKey(s),
                                   PlantedSpec.paper(12 + s, 70.0))
               for s in range(4)]  # one (16,16,16) bucket
    eng.run(tensors)
    assert eng.stats.compiles == 2  # chunk-step + refill, once each

    events = []
    mon.register_event_duration_secs_listener(
        lambda ev, dur, **kw: events.append(ev)
        if "compile" in ev or "trace" in ev else None)
    try:
        before = eng.stats
        outs = eng.run(tensors)
        delta = eng.stats.delta(before)
    finally:
        mon.clear_event_listeners()

    assert events == [], f"warm stream traced/compiled: {events}"
    assert delta.compiles == 0 and delta.refills > 0 and \
        delta.chunk_steps > 0, delta
    assert all(o is not None for o in outs)


def test_distinct_buckets_compile_two_each():
    from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                            make_msc_mesh)
    from repro.serving import MSCContinuousEngine

    mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
    eng = MSCContinuousEngine(mesh, MSCConfig(epsilon=3e-4, power_tol=1e-2),
                              slots=2)
    ts = [make_planted_tensor(jax.random.PRNGKey(i),
                              PlantedSpec.paper(mm, 70.0))
          for i, mm in enumerate((10, 14, 18, 22))]
    eng.run(ts)
    assert eng.stats.compiles == 4   # buckets 16^3 and 24^3, 2 execs each
    eng.run(ts)
    assert eng.stats.compiles == 4   # both warm


# ------------------------------------------------- engine unit layer --

class TestContinuousEngineUnits:
    def _engine(self, **kw):
        from repro.core import MSCConfig, make_msc_mesh
        from repro.serving import MSCContinuousEngine

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        return MSCContinuousEngine(mesh,
                                   MSCConfig(epsilon=3e-4, power_tol=1e-2),
                                   **kw)

    def test_rejects_bad_slots(self):
        with pytest.raises(ValueError, match="slots"):
            self._engine(slots=0)

    def test_rejects_bad_placement(self):
        with pytest.raises(ValueError, match="placement"):
            self._engine(placement="shuffle")

    def test_rejects_gateless_config(self):
        from repro.core import MSCConfig, make_msc_mesh
        from repro.serving import MSCContinuousEngine

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="power_tol"):
            MSCContinuousEngine(mesh, MSCConfig(power_tol=0.0))

    def test_rejects_gram_config(self):
        from repro.core import MSCConfig, make_msc_mesh
        from repro.serving import MSCContinuousEngine

        mesh = make_msc_mesh("flat", devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="matrix_free"):
            MSCContinuousEngine(mesh, MSCConfig(power_tol=1e-2,
                                                matrix_free=False))

    def test_starvation_bound_admits_despite_refill_batching(self):
        """refill_min_free == slots would otherwise defer admission
        until the table fully drains; the starvation bound forces it."""
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=2, refill_min_free=2, max_queue_chunks=2)
        ts = [make_planted_tensor(jax.random.PRNGKey(i),
                                  PlantedSpec.paper(14, g))
              for i, g in enumerate((30.0, 70.0, 90.0, 40.0))]
        outs = eng.run(ts)
        assert all(o is not None for o in outs)
        assert eng.stats.evictions == 4
        assert eng.stats.requests == 4

    def test_streaming_submit_step_api(self):
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=2)
        rids = [eng.submit(make_planted_tensor(jax.random.PRNGKey(i),
                                               PlantedSpec.paper(14, 70.0)))
                for i in range(3)]
        done = {}
        while eng.has_work():
            done.update(eng.step())
        assert sorted(done) == sorted(rids)
        assert eng.stats.occupancy > 0

    def test_results_in_input_order_across_buckets(self):
        from repro.core import PlantedSpec, make_planted_tensor

        eng = self._engine(slots=2)
        sizes = (14, 33, 15, 21)
        ts = [make_planted_tensor(jax.random.PRNGKey(i),
                                  PlantedSpec.paper(mm, 70.0))
              for i, mm in enumerate(sizes)]
        outs = eng.run(ts)
        for mm, res in zip(sizes, outs):
            assert res[0].mask.shape == (mm,)

    def test_permutation_compact_vs_stable(self):
        from repro.serving.msc_engine import _SlotTable

        eng = self._engine(slots=4)
        tb = _SlotTable((8, 8, 8), None, None, 4, np.float32,
                        eng._plan.mode_shapes((8, 8, 8), 4))
        tb.slot_req = [None, 7, None, 9]
        assert list(eng._permutation(tb)) == [1, 3, 0, 2]
        eng.placement = "stable"
        assert list(eng._permutation(tb)) == [0, 1, 2, 3]


# ------------------------------------------------ roofline model -----

class TestContinuousModel:
    def test_uniform_mix_no_win(self):
        r = continuous_serving_model([12] * 16, 8)
        assert r["occupancy_static"] == 1.0
        assert r["speedup"] == pytest.approx(1.0, abs=0.35)

    def test_skewed_mix_wins(self):
        r = continuous_serving_model(([60] + [12] * 7) * 2, 8)
        assert r["speedup"] > 1.4
        assert r["occupancy_continuous"] > r["occupancy_static"]

    def test_dispatch_overhead_erodes_win(self):
        hist = ([60] + [12] * 7) * 2
        free = continuous_serving_model(hist, 8, dispatch_s=0.0)
        taxed = continuous_serving_model(hist, 8, dispatch_s=10.0)
        assert taxed["speedup"] < free["speedup"]

    def test_shape_mode_charges_epilogue_per_refill(self):
        hist = ([60] + [12] * 7) * 2
        r = continuous_serving_model(hist, 8, shape=(96, 96, 96), p=8)
        assert r["refills"] < r["chunks"] + 2
        assert r["continuous_s"] > 0 and r["static_s"] > 0

    def test_embedded_in_serving_model(self):
        from repro.roofline import serving_model

        r = serving_model((24, 24, 24), 8, 8, iter_hist=[12] * 8)
        assert r["continuous"]["requests"] == 8
        assert serving_model((24, 24, 24), 8, 8)["continuous"] is None

    def test_rejects_empty_hist(self):
        with pytest.raises(ValueError):
            continuous_serving_model([], 8)


# ------------------------------------------- in-process CI matrix ----

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs >= 8 devices (CI multi-device job)")
def test_continuous_in_process():
    """Real multi-device continuous path, no subprocess; the CI job
    matrix sets MSC_MESH_SHAPE to each factorization of its 8 forced
    host devices (8x1, 4x2)."""
    from repro.core import (MSCConfig, PlantedSpec, make_planted_tensor,
                            msc_sequential, make_msc_mesh)
    from repro.serving import MSCContinuousEngine

    p, q = (int(x) for x in
            os.environ.get("MSC_MESH_SHAPE", "4x2").split("x"))
    mesh = make_msc_mesh("flat", devices=jax.devices()[:p * q], shape=(p, q))
    cfg = MSCConfig(epsilon=3e-4, power_tol=1e-2, epilogue="ring")
    eng = MSCContinuousEngine(mesh, cfg, slots=2)
    tensors = [make_planted_tensor(jax.random.PRNGKey(i),
                                   PlantedSpec.paper(mm, g))
               for i, (mm, g) in enumerate(
                   ((21, 70.0), (23, 30.0), (17, 90.0), (24, 40.0)))]
    outs = eng.run(tensors)
    before = eng.stats
    eng.run(tensors)
    assert eng.stats.delta(before).compiles == 0
    for t, res in zip(tensors, outs):
        ref = msc_sequential(t, cfg)
        for j in range(3):
            assert (res[j].mask == np.asarray(ref[j].mask)).all()
            np.testing.assert_allclose(res[j].d, np.asarray(ref[j].d),
                                       rtol=3e-5, atol=3e-5)
            assert int(res[j].power_iters_run) == int(ref[j].power_iters_run)
