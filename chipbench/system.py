"""Where the benchmark meets the system under test's model.

It builds the program's model of a configuration file, puts the
benchmark's weights into the program's parameter tree and checks that
tree against the program's own init, and reads the program's trees back
under the benchmark's leaf names, so that the reference and the
comparison never import the program.  What depends on the architecture
is ``programs/<model_type>.py``'s, found through ``arch.program``.
"""
from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import arch  # noqa: E402
from repro.models.model import build_model  # noqa: E402


def build(c: dict):
    """The program's model of configuration ``c``."""
    prog = arch.program(c)
    return build_model(prog.program_config(c), remat=False)


def program_params(c: dict, model, w: dict):
    """The benchmark's weights ``w`` in the program's tree, on the device,
    checked leaf for leaf against the tree the program's own init makes.
    The vocabulary's padding rows are zero."""
    want = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    prog = arch.program(c)
    tree = jax.jit(lambda w: prog.program_tree(c, w, model))(w)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError("the program's parameter tree changed: "
                           f"{jax.tree.structure(want)}")
    return tree


def leaves(c: dict, tree) -> dict:
    """The benchmark's names for the leaves of a program tree (params or an
    optimizer moment), with the vocabulary's padding cut off."""
    return arch.program(c).leaves(c, tree)


def leaf_norms(c: dict, tree) -> dict:
    """Norm of each leaf of a program tree, on the device, under the
    benchmark's names."""
    return {k: float(v) for k, v in _norms(json.dumps(c, sort_keys=True),
                                           tree).items()}


@functools.partial(jax.jit, static_argnums=0)
def _norms(c_json, tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in leaves(json.loads(c_json), tree).items()}
