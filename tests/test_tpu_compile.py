"""Compile-only checks of the kernels for one TPU v5e chip.

The only test file that describes the chip.  The TPU compiler is
installed with jax and compiles for a v5e that is described, not
attached, so each test here compiles at the m = 400 widths chip_smoke.py
runs, with `interpret=False`, and asserts that the executable holds the
compiled Pallas kernel (`tpu_custom_call`).  Nothing runs.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture — never at import — and every test
that needs it lives in this one file.
"""
import numpy as np
import pytest

B, M = 4, 400       # slots × the larger bucket of chip_smoke.py


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a persistent-cache entry compiled for a described chip cannot
        # be read back without one; keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, sharding, dtype=None):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, dtype or jnp.float32,
                                sharding=sharding)


def _assert_kernel(fn, *args):
    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("policy", ["fp32", "bf16_fp32"])
def test_power_iterate_chunk_compiles(one_chip, policy):
    from repro.core.power_iter import compute_dtype, dot_precision
    from repro.kernels.power_iter import power_iterate_chunk

    def chunk(s, v):
        return power_iterate_chunk(s, v, 6, interpret=False,
                                   precision=dot_precision(policy))

    _assert_kernel(chunk, _struct((B, M, M, M), one_chip,
                                  compute_dtype(policy)),
                   _struct((B, M, M), one_chip))


def test_power_matvec_compiles(one_chip):
    """The inner-sharded sweep: each device holds half of every slice's
    rows on a (p, 2) mesh."""
    from repro.core.power_iter import dot_precision
    from repro.kernels.power_iter import power_matvec

    def matvec(s, v):
        return power_matvec(s, v, interpret=False,
                            precision=dot_precision("fp32"))

    _assert_kernel(matvec, _struct((B, M, M // 2, M), one_chip),
                   _struct((B, M, M), one_chip))


@pytest.mark.parametrize("batched", [False, True],
                         ids=["unbatched", "batched"])
def test_abs_rowsum_compiles(one_chip, batched):
    from repro.core.power_iter import dot_precision
    from repro.kernels.ring import abs_rowsum

    lead = (B,) if batched else ()

    def rowsum(a, b):
        return abs_rowsum(a, b, interpret=False,
                          precision=dot_precision("fp32"))

    _assert_kernel(rowsum, _struct(lead + (M, M), one_chip),
                   _struct(lead + (M, M), one_chip))


def test_engine_kernel_chunk_step_compiles(topo, monkeypatch):
    """The continuous engine's whole chunk step with use_kernels=True —
    three modes' kernels in one shard_map region over one chip.  The
    kernel dispatch asks the default backend (the CPU here), so the test
    steers it to the chip's branch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import MSCConfig
    from repro.core.parallel import MSCChunkPlan
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("slice",))
    plan = MSCChunkPlan(mesh, MSCConfig(epsilon=3e-4, use_kernels=True))
    blocks, carries = plan.state_structs((M, M, M), B, jnp.float32)
    text = jax.jit(plan.build_step()).lower(blocks, carries).compile() \
        .as_text()
    assert "tpu_custom_call" in text
