# lint-tpu: disable-file=L004 -- quantization backend math (README: Repo lint)
"""Weight-only quantization for SERVING (inference-mode ``Int8Linear``
path, selected via ``ServingConfig(weight_dtype="int8")``).

Unlike :class:`~paddle_tpu.quantization.Int8Linear` — which swaps
sublayers and needs a calibrated activation scale — the serving model's
attention/MLP forwards consume raw ``layer.weight`` tensors inside fused
ops (``fused_norm_linear`` etc.), so there is no per-layer ``forward``
to intercept.  Instead :func:`quantize_model_weights` quantizes every
Linear-family weight IN PLACE:

* absmax per-out-channel int8 codes + f32 scales are attached to the
  layer as buffers (``weight_int8`` [in, out] i8, ``weight_scale``
  [1, out] f32) — these are the deployable artifacts, and what a TPU
  build keeps resident in HBM;
* ``layer.weight._value`` is rebound to the exact dequantization
  ``codes * scale / 127`` — the matmul-prologue dequant, materialized
  once at quantize time so every fused op and compiled step reads
  int8-representable weights without touching the model's fused-op
  plumbing.  Served math is therefore bit-identical to an on-the-fly
  prologue dequant.

The scale rule is the same ``_quantize_weight`` the QAT→int8 conversion
uses (per-channel ``FakeQuantChannelWiseAbsMax`` convention), so PTQ'd
checkpoints and serving-quantized weights cannot drift.

A compiled step takes the weights as arguments, so the rebind is seen by
its next call; the new buffers make the next step maker build a fresh
step (``models/generation.py::cached_step``).
"""
from __future__ import annotations

import logging
from typing import Optional

import jax.numpy as jnp

from ..core.tensor import Tensor
from . import _quantize_weight

__all__ = ["quantize_model_weights", "resolve_weight_dtype"]

logger = logging.getLogger("paddle_tpu.quantization.serving")

_WEIGHT_DTYPE_ALIASES = {
    None: None, "": None, "fp32": None, "float32": None, "auto": None,
    "int8": "int8", "i8": "int8", "w8": "int8", "weight_int8": "int8",
}

# Layer types whose 2-D [in, out] ``weight`` participates in matmuls.
# Norm weights / embedding tables are plain Parameters on other layer
# types and are deliberately untouched (standard weight-only recipes
# keep them full precision).
_LINEAR_TYPES = ("Linear", "ColumnParallelLinear", "RowParallelLinear")


def resolve_weight_dtype(name: Optional[str]) -> Optional[str]:
    """Canonical weight-quant scheme, or None for full precision."""
    key = name.lower() if isinstance(name, str) else name
    try:
        return _WEIGHT_DTYPE_ALIASES[key]
    except KeyError:
        raise ValueError(
            f"unsupported weight_dtype {name!r}; serving weight-only "
            f"quantization supports int8 (aliases: i8, w8) or "
            f"fp32/None") from None


def quantize_model_weights(model, weight_dtype: Optional[str] = None):
    """Quantize ``model``'s Linear-family weights in place (absmax
    per-out-channel int8).  Idempotent: re-applying the same scheme is a
    no-op; applying a DIFFERENT scheme to an already-quantized model
    raises (the original fp32 weights are gone — requantizing int8
    codes at another width would silently compound error).

    Returns a report dict: ``layers`` quantized, ``fp32_bytes`` the
    weights occupied before, ``quant_bytes`` the int8 codes + scales
    a deployment keeps resident.
    """
    scheme = resolve_weight_dtype(weight_dtype)
    prior = getattr(model, "_serving_weight_dtype", None)
    if scheme is None:
        if prior is not None:
            raise ValueError(
                f"model weights already quantized to {prior}; cannot "
                "restore full precision (reload the checkpoint)")
        return {"layers": 0, "fp32_bytes": 0, "quant_bytes": 0}
    if prior is not None:
        if prior == scheme:
            return dict(model._serving_weight_quant_report)
        raise ValueError(
            f"model weights already quantized to {prior}; cannot "
            f"requantize to {scheme}")

    layers = fp32_bytes = quant_bytes = 0
    for layer in model.sublayers(include_self=True):
        if type(layer).__name__ not in _LINEAR_TYPES:
            continue
        w = getattr(layer, "weight", None)
        if w is None or w._value.ndim != 2:
            continue
        wv = w._value.astype(jnp.float32)
        codes, scale = _quantize_weight(wv, quant_axis=1,
                                        per_channel=True)
        layer.register_buffer("weight_int8", Tensor(codes))
        layer.register_buffer("weight_scale", Tensor(scale))
        # the matmul-prologue dequant, materialized at quantize time
        w._value = (codes.astype(jnp.float32)
                    * (scale / 127.0)).astype(wv.dtype)
        layers += 1
        fp32_bytes += int(wv.size) * 4
        quant_bytes += int(codes.size) + int(scale.size) * 4

    report = {"layers": layers, "fp32_bytes": fp32_bytes,
              "quant_bytes": quant_bytes}
    model._serving_weight_dtype = scheme
    model._serving_weight_quant_report = dict(report)
    logger.info(
        "weight-only quant: %d linear layers -> %s (%.2f MiB -> "
        "%.2f MiB resident)",
        layers, scheme, fp32_bytes / 2**20, quant_bytes / 2**20)
    return report
