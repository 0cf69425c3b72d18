"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's capabilities.

Brand-new design on JAX/XLA/Pallas — see SURVEY.md at the repo root for the mapping to
the reference (`/root/reference`, PaddlePaddle ~v2.4). The public surface mirrors
`paddle.*` so reference user code ports with an import swap.
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os

import jax as _jax

# Multi-process launch contract (python -m paddle_tpu.distributed.launch):
# jax.distributed.initialize MUST run before anything touches the XLA
# backend, and importing this package is the first thing every worker
# does — so the bootstrap lives here. endpoints[0] hosts the coordination
# service (the reference's TCPStore-rendezvous slot, parallel.py:108).
from ._jax_compat import distributed_is_initialized as _dist_is_init

if int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1 \
        and _os.environ.get("PADDLE_TRAINER_ENDPOINTS") \
        and "PADDLE_LOCAL_RANK" in _os.environ \
        and "_PADDLE_TPU_BOOTSTRAPPED" not in _os.environ \
        and not _dist_is_init():
    # PADDLE_LOCAL_RANK marks a launcher-SPAWNED worker: stale shell
    # exports of the other contract vars must not hijack an unrelated
    # process (e.g. the launcher itself) into the coordination service.
    # _PADDLE_TPU_BOOTSTRAPPED (set below, inherited by ANY subprocess a
    # worker spawns — pipe-command data generators, PS servers) keeps
    # those children from re-joining the coordination service with a
    # duplicate process_id on import.
    _jax.distributed.initialize(
        coordinator_address=_os.environ["PADDLE_TRAINER_ENDPOINTS"]
        .split(",")[0],
        num_processes=int(_os.environ["PADDLE_TRAINERS_NUM"]),
        process_id=int(_os.environ.get("PADDLE_TRAINER_ID", "0")))
    _os.environ["_PADDLE_TPU_BOOTSTRAPPED"] = "1"

# Paddle dtype semantics need real int64/float64 (python ints -> int64 tensors).
# Weak typing keeps python scalars from promoting compute dtypes, and all perf-path
# code is explicit f32/bf16, so this does not drag float64 onto the MXU.
_jax.config.update("jax_enable_x64", True)

from .framework import (  # noqa: F401
    Tensor, to_tensor, no_grad, enable_grad, is_grad_enabled, set_grad_enabled,
    grad,
    seed, get_rng_state, set_rng_state, set_flags, get_flags,
    set_default_dtype, get_default_dtype,
    CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace,
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32, float64,
    complex64, complex128,
)
from .framework.tensor import Parameter  # noqa: F401
from .framework.dtype import bool_ as bool  # noqa: F401  (paddle.bool)

from .ops import *  # noqa: F401,F403  — the paddle.* tensor-op surface
from . import ops  # noqa: F401

# submodules populated by later milestones are imported lazily to keep import light
from . import framework  # noqa: F401


def __getattr__(name):
    import importlib
    _lazy = {
        "nn", "optimizer", "amp", "autograd", "io", "vision", "static", "jit",
        "distributed", "incubate", "models", "kernels", "profiler", "utils",
        "metric", "device", "hapi", "distribution", "sparse", "fft", "signal",
        "text", "audio", "quantization", "inference", "geometric", "hub",
        "onnx", "observability",
    }
    if name in _lazy:
        try:
            mod = importlib.import_module(f".{name}", __name__)
        except ModuleNotFoundError as e:
            # keep hasattr()/getattr(default) semantics for unbuilt
            # subpackages — but only when it's this subpackage that's absent,
            # not a genuine missing dependency inside an existing one
            if e.name == f"{__name__}.{name}":
                raise AttributeError(
                    f"module 'paddle_tpu' has no attribute {name!r}") from e
            raise
        globals()[name] = mod
        return mod
    # top-level classes/fns that live in lazily-imported packages
    _lazy_attrs = {
        "Model": ("hapi", "Model"),
        "summary": ("hapi", "summary"),
        "callbacks": ("hapi", "callbacks"),
        "flops": ("hapi", "flops"),
    }
    if name in _lazy_attrs:
        mod_name, attr = _lazy_attrs[name]
        mod = importlib.import_module(f".{mod_name}", __name__)
        val = getattr(mod, attr)
        globals()[name] = val
        return val
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


# save/load + seed surface
from .framework.io import save, load, CheckpointCorruptError  # noqa: F401,E402

# top-level parity aliases (reference python/paddle/__init__.py __all__)
from .nn.layer.layers import ParamAttr  # noqa: E402,F401
from .framework.place import TPUPlace as NPUPlace  # noqa: E402,F401
from .framework.dtype import DType as dtype  # noqa: E402,F401
from .framework.random import (  # noqa: E402,F401
    get_rng_state as get_cuda_rng_state,
    set_rng_state as set_cuda_rng_state,
)
from .static import enable_static, disable_static  # noqa: E402,F401
from .distributed.parallel import DataParallel  # noqa: E402,F401
