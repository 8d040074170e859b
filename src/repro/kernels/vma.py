"""Pallas-call plumbing for kernels called inside a checked `jax.shard_map`.

A checked shard_map types every value with the mesh axes it varies over
(its vma).  Two things follow for a pallas_call in such a region:

* its outputs must declare their vma — those of the operands;
* the HLO interpreter (`interpret=True`) cannot evaluate the kernel
  there: the kernel body mixes operand blocks that vary with indices
  and constants that do not, which the check refuses.  The TPU
  interpreter evaluates each grid step on concrete per-device values
  and has no such limit, but it is about 100x slower, so it is used
  only where an operand varies.

On the chip the kernels compile (`interpret=False`) and neither applies
beyond the declared vma.
"""
from __future__ import annotations

import jax


def operand_vma(*operands) -> frozenset:
    """Union of the mesh axes the operands vary over (empty outside
    shard_map)."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """Output struct varying over every mesh axis any operand varies over."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=operand_vma(*operands))


def interpret_mode(interpret: bool, *operands):
    """pallas_call's `interpret` argument: False compiles for the TPU;
    True picks the HLO interpreter outside shard_map and the TPU
    interpreter inside it."""
    if not interpret:
        return False
    if operand_vma(*operands):
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.InterpretParams()
    return True
