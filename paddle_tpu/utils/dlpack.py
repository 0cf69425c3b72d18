"""paddle.utils.dlpack parity (reference ``utils/dlpack.py:105`` —
to_dlpack/from_dlpack over the C++ tensor bridge).

TPU-native: jax arrays speak the standard ``__dlpack__`` protocol, so
`from_dlpack` ingests any dlpack producer (torch, numpy, cupy, ...)
zero-copy where the PJRT backend allows. Export (`to_dlpack`) is
zero-copy when the backend implements external references; where it does
not we fall back to a host numpy copy — semantics preserved, zero-copy
lost.
"""
import jax
import numpy as np

from ..framework.tensor import Tensor


def to_dlpack(x):
    """Tensor -> DLPack capsule (or capsule-bearing array)."""
    arr = x._value if isinstance(x, Tensor) else x
    try:
        return arr.__dlpack__()
    except Exception:
        # backend without PJRT external references: export via host copy
        return np.asarray(arr).__dlpack__()


def from_dlpack(dlpack):
    """DLPack capsule / any ``__dlpack__`` producer -> Tensor."""
    if hasattr(dlpack, "__dlpack__"):
        try:
            return Tensor(jax.dlpack.from_dlpack(dlpack))
        except Exception:
            return Tensor(jax.numpy.asarray(np.from_dlpack(dlpack)))
    # raw capsule: numpy can consume capsules portably
    return Tensor(jax.numpy.asarray(np.from_dlpack(_CapsuleWrap(dlpack))))


class _CapsuleWrap:
    """np.from_dlpack expects an object with __dlpack__()."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, stream=None):
        return self._capsule

    def __dlpack_device__(self):
        return (1, 0)  # kDLCPU
