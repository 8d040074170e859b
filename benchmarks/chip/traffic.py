"""The one generator of every traffic mix: the tensors and their order.

A mix is a data file, `traffic/<name>.json`:

  loop              "closed": callers that each wait for their answer
  callers_per_slot  callers per engine slot
  gamma             the planted strength: {"value": g} for every tensor,
                    or {"times_m": a} for g = a·m
  pool              distinct tensors made from the seed and cycled
  warmup_s          seconds of the mix run before the window opens
  why               one line: why the mix exists

Tensors are the paper's planted cube (arXiv 2309.17383, §IV):
T = γ·w⊗u⊗v + N(0, 1), each factor 1/√l on the first l indices of its
mode, with m and l = 10% of m from the configuration.  The pool is made
on the device in one jitted call and copied to the host once, where a
user's tensors would come from.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def gamma_of(traffic: dict, m: int) -> float:
    rule = traffic["gamma"]
    if "value" in rule:
        return float(rule["value"])
    return float(rule["times_m"]) * m


@partial(jax.jit, static_argnames=("n", "m", "l"))
def _planted_pool(key, gamma, *, n, m, l):
    t = jax.random.normal(key, (n, m, m, m), jnp.float32)
    return t.at[:, :l, :l, :l].add(gamma / (l * np.sqrt(l)))


def make_pool(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """(pool, m, m, m) float32 host array of distinct planted tensors."""
    m = int(config["m"])
    pool = _planted_pool(jax.random.fold_in(key_of(seed), 1),
                         jnp.float32(gamma_of(traffic, m)),
                         n=int(traffic["pool"]), m=m, l=int(config["l"]))
    host = np.asarray(jax.device_get(pool))
    del pool
    return host


def pool_order(traffic: dict, seed: int) -> np.ndarray:
    """The order in which requests cycle through the pool."""
    rng = np.random.default_rng([int(seed), 2])
    return rng.permutation(int(traffic["pool"]))

