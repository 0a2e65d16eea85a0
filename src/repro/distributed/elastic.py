"""Elastic scaling + failure handling (DESIGN.md §9).

The contract at 1000+ nodes: when a chip/host drops, the job restarts on the
surviving device set; the runtime must (1) build the largest usable mesh
from what's alive, (2) re-shard the latest checkpoint onto it, (3) resume
the data stream at the checkpointed step. Steps (1)–(2) are implemented and
tested here on CPU fake devices; the detection/respawn layer is the cluster
scheduler's job (GKE/Borg restart policy) — see train.py --resume auto.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.models import model as M


def best_mesh_shape(n_devices: int, model_parallel: int) -> tuple:
    """Largest (data, model) grid with fixed model parallelism that fits the
    surviving device count (drop stragglers beyond the largest full grid)."""
    model = min(model_parallel, n_devices)
    while n_devices % model:
        model -= 1
    data = n_devices // model
    return (data, model)


def build_elastic_mesh(devices: Optional[Sequence] = None,
                       model_parallel: int = 16) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    data, model = best_mesh_shape(len(devices), model_parallel)
    used = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(used, ("data", "model"))


def reshard_state(state, cfg, pcfg, new_mesh: Mesh):
    """Re-shard a (params, opt_state) pytree onto a new mesh (after failure
    or scale-up). Works from host arrays or differently-sharded jax.Arrays."""
    params = state["params"]
    pspecs = M.param_pspecs(cfg, pcfg, params)
    from repro.launch.dryrun import sanitize_spec  # divisibility guard

    def put(tree, specs):
        return jax.tree.map(
            lambda x, sp: jax.device_put(
                np.asarray(x),
                NamedSharding(new_mesh, sanitize_spec(new_mesh, sp, x.shape)),
            ),
            tree,
            specs,
            is_leaf=lambda t: not isinstance(t, dict),
        )

    out = dict(state)
    out["params"] = put(params, pspecs)
    if "opt_state" in state:
        os_ = state["opt_state"]
        out["opt_state"] = dict(
            os_,
            mu=put(os_["mu"], pspecs),
            nu=put(os_["nu"], pspecs),
        )
    return out
