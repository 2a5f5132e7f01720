# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""paddle.utils.dlpack (reference: paddle/fluid/framework/dlpack_tensor.cc):
zero-copy tensor exchange with other frameworks via the DLPack protocol."""
from __future__ import annotations

import jax.numpy as jnp

from ..core.tensor import Tensor


def to_dlpack(x: Tensor):
    return x._value.__dlpack__()


class _CapsuleHolder:
    """Adapter for RAW PyCapsules (torch.utils.dlpack.to_dlpack returns
    one): newer jax/numpy only accept objects with __dlpack__/
    __dlpack_device__.  A capsule carries no device info, so this assumes
    host memory (kDLCPU) — raw-capsule handoff between frameworks is a
    host-side path; device arrays come in as __dlpack__-bearing objects."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, stream=None, **kwargs):
        return self._capsule

    def __dlpack_device__(self):
        return (1, 0)  # kDLCPU, device 0


def from_dlpack(capsule) -> Tensor:
    if hasattr(capsule, "__dlpack__"):
        arr = jnp.from_dlpack(capsule)
    else:  # raw PyCapsule
        import numpy as np

        arr = jnp.asarray(np.from_dlpack(_CapsuleHolder(capsule)))
    return Tensor(arr)
