"""Process bootstrap + DataParallel.

Parity: ``/root/reference/python/paddle/distributed/parallel.py:108
init_parallel_env`` (TCPStore rendezvous + default ProcessGroup) and
``python/paddle/fluid/dygraph/parallel.py`` DataParallel (+ C++ EagerReducer,
collective/reducer.h:42).

TPU-native: rendezvous is ``jax.distributed.initialize`` (its coordination
service is the TCPStore analog); the default "process group" is the dp axis of
the global mesh. DataParallel needs no bucketing reducer — in the compiled train
step the batch is sharded over dp, so XLA emits one fused reduce-scatter/all-
reduce for the gradient tree at the optimum point in the schedule, which is
exactly what EagerReducer's group-by-size fusion approximates by hand.
"""
from __future__ import annotations

import os

import jax

from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer
from . import env as env_mod
from .mesh import build_mesh, set_global_mesh, get_global_mesh, Group
from .collective import _set_default_group


_initialized = False
_process_store = None


def init_parallel_env():
    """Bootstrap multi-process (multi-host) or single-process multi-device.

    Multi-process: ``jax.distributed.initialize`` against endpoint[0] (the
    coordination service plays the reference's TCPStore rendezvous role);
    the global mesh then spans every process's devices. When the launcher
    exported ``PADDLE_STORE_ENDPOINT`` this process also connects a client
    to the launcher-hosted native TCPStore — the channel the host-side
    object collectives (broadcast_object_list / scatter_object_list) and
    barriers ride (parallel.py:108 parity).
    """
    global _initialized, _process_store
    if _initialized:
        return env_mod.ParallelEnv()
    from .._jax_compat import distributed_is_initialized
    world = env_mod.get_world_size()
    if world > 1 and "PADDLE_TRAINER_ENDPOINTS" in os.environ \
            and not distributed_is_initialized():
        # normally already done at paddle_tpu import (the bootstrap must
        # precede any XLA backend touch); kept for direct callers
        eps = env_mod.get_endpoints()
        jax.distributed.initialize(
            coordinator_address=eps[0],
            num_processes=world,
            process_id=env_mod.get_rank())
    store_ep = os.environ.get("PADDLE_STORE_ENDPOINT")
    if world > 1 and store_ep:
        from .store import TCPStore
        host, port = store_ep.rsplit(":", 1)
        _process_store = TCPStore(host, int(port), is_master=False,
                                  world_size=world)
    # under an elastic relaunch controller, publish this worker's liveness
    # lease so a wedged (not just dead) worker is detected (no-op otherwise)
    from .fleet.elastic import maybe_start_worker_heartbeat
    maybe_start_worker_heartbeat()
    mesh = build_mesh(dp=len(jax.devices()))
    set_global_mesh(mesh)
    _set_default_group(Group("dp", mesh))
    _initialized = True
    return env_mod.ParallelEnv()


def get_process_store():
    """The cross-process TCPStore client (multi-process launches), or None."""
    return _process_store


def is_initialized():
    return _initialized


class DataParallel(Layer):
    """paddle.DataParallel parity wrapper.

    Eager single-controller: forward passes through; gradients are correct by
    construction once the step runs under the compiled dp-sharded path
    (fleet.distributed_model + to_static / ParallelTrainStep). The
    comm_buffer_size/last_comm_buffer_size knobs are accepted for parity; XLA's
    scheduler owns fusion so they are advisory no-ops.
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        self.find_unused_parameters = find_unused_parameters
        self.group = group

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def state_dict(self, *a, **kw):
        return self._layers.state_dict(*a, **kw)

    def set_state_dict(self, state_dict, **kw):
        return self._layers.set_state_dict(state_dict, **kw)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        pass  # grads reduced inside the compiled step (see class docstring)


ParallelEnv = env_mod.ParallelEnv

# paddle.distributed.spawn moved to its own module (store-backed rendezvous);
# re-exported here for the historical import path
from .spawn import spawn, SpawnContext  # noqa: F401,E402
