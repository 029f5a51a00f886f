"""Random weights of a configuration, made from the run's seed.

One jitted call makes every leaf on the device, in float32, under the
benchmark's own names.  The leaves and how each is drawn are the
architecture's (``shapes`` and ``init`` of ``reference/<model_type>.py``).
The system under test gets them through ``system.program_params`` and
the plain reference reads them as they are, so both start from the same
numbers without the reference taking anything the program made.
"""
from __future__ import annotations

import functools
import json

import jax

import arch


def seed_key(seed: int):
    """A JAX key from any non-negative seed, also one above 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@functools.lru_cache(maxsize=4)
def _maker(config_json: str):
    c = json.loads(config_json)
    ref = arch.reference(c)
    leaves = sorted(ref.shapes(c).items())

    @jax.jit
    def make(key):
        return {name: ref.init(name, jax.random.fold_in(key, i), shape)
                for i, (name, shape) in enumerate(leaves)}
    return make


def make(c: dict, seed: int) -> dict:
    """Every leaf of configuration ``c`` from ``seed``, in one jitted call."""
    return _maker(json.dumps(c, sort_keys=True))(seed_key(seed))
