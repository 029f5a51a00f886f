"""Disaggregated generation/training placement — AReaL's defining layout.

The paper decouples rollout and trainer workers onto disjoint GPU pools
(Sec 4, Sec 7.1: 75/25 inference/training).  On TPU this maps to two
*submeshes* of one device pool: weights flow trainer -> rollout via
``jax.device_put`` (ICI/DCN), the analogue of AReaL's parameter push over
NVLink/TCP; trajectories flow rollout -> trainer host-side (the replay
buffer is host memory, as in the paper).

``split_devices`` builds the two meshes; ``push_weights`` is the
cross-mesh transfer; ``demo`` exercises the loop on local host devices
(run with XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def split_devices(train_fraction: float = 0.25, *, model_parallel: int = 1,
                  devices=None) -> Tuple[Mesh, Mesh]:
    """Partition the device pool into (rollout_mesh, trainer_mesh)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    n_train = max(model_parallel, int(round(n * train_fraction)))
    n_train -= n_train % model_parallel
    n_roll = n - n_train
    n_roll -= n_roll % model_parallel
    assert n_roll > 0 and n_train > 0, "pool too small for the split"

    def mk(devs):
        arr = np.array(devs).reshape(len(devs) // model_parallel, model_parallel)
        return Mesh(arr, ("data", "model"))

    return mk(devices[:n_roll]), mk(devices[n_roll:n_roll + n_train])


def host_weights(params):
    """Device -> host copy of a param tree as plain numpy (DESIGN.md
    §Fleet runtime): the picklable form the fleet supervisor ships over
    worker transports when publishing one trainer version to MANY
    rollout subscribers — the cross-PROCESS analogue of
    ``push_weights``'s cross-submesh device_put.  An RPC backend would
    serialize exactly this tree."""
    return jax.tree.map(np.asarray, params)


def stream_weights(params, *, version: int, base=None,
                   base_version=None, encoding: str = "delta",
                   chunk_elems: int = 65536):
    """Streaming form of the trainer→rollout publication (DESIGN.md
    §Streaming weight publication, §Chunk framing): device→host copy of
    the param tree plus delta encoding against ``base`` — the previous
    published HOST tree — framed as a ``WeightStream`` of chunk
    messages.  Returns ``(host_tree, stream)``; the caller keeps
    ``host_tree`` as the next publication's base and ships the stream's
    messages over whatever transport reaches the rollout side (the
    in-process queue of ``ThreadedRuntime`` or the fleet ``Transport``).
    With ``base=None`` (first publication) the stream falls back to
    base-free ``full`` chunks."""
    from repro.core.weights import encode_stream
    host = host_weights(params)
    stream = encode_stream(host, version=version, base=base,
                           base_version=base_version, encoding=encoding,
                           chunk_elems=chunk_elems)
    return host, stream


def push_weights(params, rollout_mesh: Mesh, specs=None):
    """Trainer -> rollout weight publication: one device_put of the
    (possibly resharded) param tree onto the rollout submesh.  With
    interruptible generation this happens off the training critical path
    (the trainer proceeds; rollout workers re-prefill on arrival).
    ``stream_weights`` is the incremental host-side alternative
    (DESIGN.md §Streaming weight publication)."""
    if specs is None:
        sharding = NamedSharding(rollout_mesh, P())
        return jax.device_put(params, sharding)
    return jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(rollout_mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P)))


def demo(n_steps: int = 3):
    """Round-trip a tiny model's weights trainer->rollout and run a
    decode step on the rollout mesh (requires >=2 local devices)."""
    import jax.numpy as jnp

    from repro.configs import get_model_config, reduced
    from repro.models.model import build_model

    roll_mesh, train_mesh = split_devices(0.5)
    cfg = reduced(get_model_config("areal-qwen-1.5b"))
    model = build_model(cfg, remat=False)
    with jax.set_mesh(train_mesh):
        params = model.init(jax.random.key(0))
    for step in range(n_steps):
        # (trainer would update params here)
        roll_params = push_weights(params, roll_mesh)
        with jax.set_mesh(roll_mesh):
            cache = model.init_cache(4, 32)
            toks = jnp.zeros((4, 8), jnp.int32)
            logits, cache = model.prefill(roll_params, toks, cache)
            logits, cache = model.decode_step(
                roll_params, jnp.argmax(logits, -1).astype(jnp.int32), cache)
        print(f"step {step}: decode on rollout mesh ok, "
              f"logits finite={bool(jnp.isfinite(logits).all())}")


if __name__ == "__main__":
    demo()
