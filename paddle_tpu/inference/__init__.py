# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""paddle.inference: the serving runtime (reference:
paddle/fluid/inference/api/analysis_predictor.cc + paddle_inference_api.h).

TPU-native: the "optimized inference program" IS the jit.save StableHLO
artifact; AnalysisPredictor's 40-pass pipeline collapses into XLA compilation
(with a persistent compile cache).  Zero-copy handles wrap device arrays.
"""
from __future__ import annotations

import enum
import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Config", "create_predictor", "create_serving_endpoint",
           "DistConfig", "DistModel",
           "Predictor", "PredictorPool", "get_version", "DataType",
           "PlaceType", "PrecisionType", "Tensor", "get_trt_compile_version",
           "get_trt_runtime_version", "get_num_bytes_of_data_type",
           "load_c_api"]


def load_c_api():
    """Build + load the stable C inference ABI (reference capi_exp/
    pd_inference_api.h analog; see inference/capi.py)."""
    from .capi import load_c_api as _load

    return _load()


def get_version():
    import paddle_tpu

    return paddle_tpu.__version__


class DataType(enum.Enum):
    """paddle_infer.DataType (reference: paddle_inference_api.h PaddleDType);
    FLOAT16/BFLOAT16 added — TPU serving is natively bf16."""
    FLOAT32 = 0
    INT64 = 1
    INT32 = 2
    UINT8 = 3
    INT8 = 4
    FLOAT16 = 5
    BFLOAT16 = 6
    BOOL = 7


class PlaceType(enum.Enum):
    """paddle_infer.PlaceType (reference: paddle_tensor.h).  GPU enums kept
    for API parity; on this backend everything placed on an accelerator is
    the TPU via PJRT."""
    UNK = -1
    CPU = 0
    GPU = 1
    XPU = 2
    NPU = 3
    TPU = 4


class PrecisionType(enum.Enum):
    """paddle_infer.PrecisionType (reference: paddle_analysis_config.h)."""
    Float32 = 0
    Int8 = 1
    Half = 2
    Bfloat16 = 3


_DTYPE_BYTES = {DataType.FLOAT32: 4, DataType.INT64: 8, DataType.INT32: 4,
                DataType.UINT8: 1, DataType.INT8: 1, DataType.FLOAT16: 2,
                DataType.BFLOAT16: 2, DataType.BOOL: 1}


def get_num_bytes_of_data_type(dtype: "DataType") -> int:
    """reference: paddle/fluid/inference/api/paddle_tensor.h
    paddle_infer::GetNumBytesOfDataType."""
    return _DTYPE_BYTES[DataType(dtype)]


def get_trt_compile_version():
    """No TensorRT on TPU: the compile-time engine is XLA.  (0, 0, 0)
    mirrors the reference's return when built without TRT
    (paddle/fluid/inference/api/analysis_predictor.cc GetTrtCompileVersion)."""
    return (0, 0, 0)


def get_trt_runtime_version():
    return (0, 0, 0)


class Config:
    """AnalysisConfig analog."""

    def __init__(self, prog_file=None, params_file=None):
        self.prog_file = prog_file
        self.params_file = params_file
        self._model_dir = None
        self._compile_cache_dir = None
        self._memory_pool_mb = 0

    def set_model(self, prog_file, params_file=None):
        self.prog_file = prog_file
        self.params_file = params_file

    def set_model_dir(self, d):
        self._model_dir = d

    def model_dir(self):
        return self._model_dir

    def enable_compile_cache(self, cache_dir):
        """Persistent XLA compile cache (the TRT engine-cache analog)."""
        self._compile_cache_dir = cache_dir

    # accepted-and-ignored GPU-era toggles for parity
    def enable_use_gpu(self, memory_pool_mb=100, device_id=0):
        self._memory_pool_mb = memory_pool_mb

    def disable_gpu(self):
        pass

    def enable_memory_optim(self):
        pass

    def switch_ir_optim(self, flag=True):
        pass

    def enable_tensorrt_engine(self, **kwargs):
        # XLA is the engine; accepted for API parity.  But a precision
        # request is a quantization decision the reference would honor
        # (analysis_predictor.cc:975 TensorRT int8 path) — dropping it
        # silently would change serving numerics, so say so.
        precision = kwargs.get("precision_mode")
        if precision is not None and "int8" in str(precision).lower():
            import warnings

            warnings.warn(
                "enable_tensorrt_engine(precision_mode=int8) is ignored: "
                "XLA serves this model at its trained precision; use "
                "paddle_tpu.quantization (PTQ/QAT) for int8")

    def set_cpu_math_library_num_threads(self, n):
        pass


class _IOHandle:
    """ZeroCopyTensor analog."""

    def __init__(self, name):
        self.name = name
        self._array = None

    def reshape(self, shape):
        pass

    def copy_from_cpu(self, data: np.ndarray):
        self._array = jnp.asarray(data)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._array)

    def share_external_data(self, data):
        self._array = data._value if hasattr(data, "_value") else data

    @property
    def shape(self):
        return list(self._array.shape) if self._array is not None else None

    def type(self):
        if self._array is None:
            return DataType.FLOAT32
        name = str(self._array.dtype)
        return {"float32": DataType.FLOAT32, "int64": DataType.INT64,
                "int32": DataType.INT32, "uint8": DataType.UINT8,
                "int8": DataType.INT8, "float16": DataType.FLOAT16,
                "bfloat16": DataType.BFLOAT16,
                "bool": DataType.BOOL}.get(name, DataType.FLOAT32)


# public name: paddle.inference.Tensor is the reference's ZeroCopyTensor
# handle type (paddle/fluid/inference/api/paddle_tensor.h) — users touch it
# via predictor.get_input_handle(); exported so isinstance checks port over.
Tensor = _IOHandle


def _load_exported(config: Config):
    """Shared model-loading path for Predictor and DistModel: honors the
    persistent compile cache, loads the jit-saved artifact."""
    from ..jit import load as jit_load

    if config._compile_cache_dir:
        from ..core.compile_cache import enable_compile_cache

        # JAX_COMPILATION_CACHE_DIR, where set, wins over the config's
        enable_compile_cache(config._compile_cache_dir)
    return jit_load(config.prog_file or config._model_dir)


class Predictor:
    def __init__(self, config: Config):
        self.config = config
        self._loaded = _load_exported(config)
        n_in = len(self._loaded._exported.in_avals) if hasattr(
            self._loaded._exported, "in_avals") else 1
        self._inputs = {f"input_{i}": _IOHandle(f"input_{i}")
                        for i in range(n_in)}
        self._outputs: Dict[str, _IOHandle] = {}

    def get_input_names(self) -> List[str]:
        return list(self._inputs)

    def get_input_handle(self, name) -> _IOHandle:
        return self._inputs[name]

    def get_output_names(self) -> List[str]:
        return list(self._outputs) or ["output_0"]

    def get_output_handle(self, name) -> _IOHandle:
        return self._outputs.setdefault(name, _IOHandle(name))

    def run(self, inputs: Optional[list] = None):
        """ZeroCopyRun: execute the compiled program."""
        if inputs is not None:
            arrs = [x._value if hasattr(x, "_value") else jnp.asarray(x)
                    for x in inputs]
        else:
            arrs = [h._array for h in self._inputs.values()]
        out = self._loaded._exported.call(*arrs)
        leaves = jax.tree_util.tree_leaves(out)
        for i, leaf in enumerate(leaves):
            self.get_output_handle(f"output_{i}")._array = leaf
        if inputs is not None:
            from ..core.tensor import Tensor

            return [Tensor(l) for l in leaves]
        return True

    def clone(self):
        return Predictor(self.config)

    def clear_intermediate_tensor(self):
        pass


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def create_serving_endpoint(model, config=None, **generate_defaults):
    """Continuous-batching LLM front door: a Predictor-shaped
    :class:`paddle_tpu.serving.Endpoint` over a live causal LM (the
    Predictor above serves jit.save artifacts; this serves token
    streams with iteration-level batching — see paddle_tpu/serving/).

    ``model`` may also be a prebuilt :class:`paddle_tpu.serving.Engine`
    or a :class:`paddle_tpu.serving.Router` fleet (``config`` must then
    be None — a prebuilt engine already carries its config).
    ``config`` is a :class:`paddle_tpu.serving.ServingConfig`;
    ``generate_defaults`` (eos_token_id, max_new_tokens, ...) apply to
    every request unless overridden per call."""
    from ..serving import Endpoint

    return Endpoint(model, config, **generate_defaults)


class PredictorPool:
    def __init__(self, config: Config, size: int = 1):
        self._predictors = [create_predictor(config) for _ in range(size)]

    def retrieve(self, idx) -> Predictor:
        return self._predictors[idx]


class DistConfig:
    """Distributed-inference settings (reference:
    paddle/fluid/distributed/fleet_executor/dist_model.h DistModelConfig —
    ranks/endpoints for the interceptor runtime).  TPU-native: serving
    shards one compiled program over a device mesh, so the knobs are the
    mesh axes rather than endpoints."""

    def __init__(self):
        self.batch_axis = "dp"
        self.devices = None      # default: all local devices
        self.carrier_id = "inference"
        self.rank = 0
        self.nranks = 1
        self._enabled = True

    def enable_dist_model(self, flag=True):
        self._enabled = bool(flag)

    def set_ranks(self, nranks, rank):
        self.nranks, self.rank = int(nranks), int(rank)


class DistModel:
    """Sharded serving (reference: dist_model.cc DistModel::Run — the
    distributed inference entry over the fleet executor).  The loaded
    program executes once across a mesh with the batch dim sharded over
    the data axis; parameters are replicated (TP-sharded serving reuses
    the training shardings via fleet + a normal compiled call instead)."""

    def __init__(self, config: Config, dist_config: DistConfig = None):
        self.config = config
        self.dist_config = dist_config or DistConfig()
        self._loaded = _load_exported(config)
        devs = self.dist_config.devices or jax.devices()
        import numpy as np

        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        self._mesh = Mesh(np.asarray(devs),
                          (self.dist_config.batch_axis,))
        self._batch_sharding = NamedSharding(
            self._mesh, PartitionSpec(self.dist_config.batch_axis))

    def run(self, inputs):
        """Batch-sharded execution; returns output Tensors.  The shardings
        actually applied to each input are kept on
        ``last_input_shardings`` for observability/tests."""
        from ..core.tensor import Tensor

        arrs = []
        self.last_input_shardings = []
        n_dev = len(self._mesh.devices.ravel())
        for x in inputs:
            v = x._value if hasattr(x, "_value") else jnp.asarray(x)
            if self.dist_config._enabled and v.ndim                     and v.shape[0] % n_dev == 0:
                v = jax.device_put(v, self._batch_sharding)
            arrs.append(v)
            self.last_input_shardings.append(getattr(v, "sharding", None))
        out = self._loaded._exported.call(*arrs)
        return [Tensor(leaf) for leaf in jax.tree_util.tree_leaves(out)]
