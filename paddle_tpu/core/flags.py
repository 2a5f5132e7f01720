"""Global flag system.

Capability parity with the reference's exported gflags
(/root/reference/paddle/fluid/platform/flags.cc, surfaced via
global_value_getter_setter.cc and FLAGS_* env vars): one typed registry,
settable via paddle_tpu.set_flags or FLAGS_<name> environment variables.
"""
from __future__ import annotations

import os
from typing import Any, Dict


class _Flag:
    __slots__ = ("name", "value", "default", "type", "help")

    def __init__(self, name, default, help=""):
        self.name = name
        self.default = default
        self.type = type(default)
        self.help = help
        env = os.environ.get(f"FLAGS_{name}")
        self.value = self._parse(env) if env is not None else default

    def _parse(self, text: str):
        if self.type is bool:
            return text.lower() in ("1", "true", "yes", "on")
        return self.type(text)


_REGISTRY: Dict[str, _Flag] = {}


def define_flag(name: str, default, help: str = ""):
    if name not in _REGISTRY:
        _REGISTRY[name] = _Flag(name, default, help)
    return _REGISTRY[name]


def _canon(name: str) -> str:
    # paddle.get_flags/set_flags take "FLAGS_<name>" keys; the registry
    # stores bare names.  Accept both.
    return name[6:] if name.startswith("FLAGS_") else name


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: _REGISTRY[_canon(n)].value for n in names}


def set_flags(flags: Dict[str, Any]):
    for name, value in flags.items():
        name = _canon(name)
        if name not in _REGISTRY:
            raise KeyError(f"unknown flag {name!r}")
        flag = _REGISTRY[name]
        flag.value = flag._parse(value) if isinstance(value, str) else flag.type(value)


def flag(name: str):
    return _REGISTRY[name].value


# Core flags (reference: platform/flags.cc).
define_flag("check_nan_inf", False, "check every op output for nan/inf")
define_flag("eager_op_jit", True, "jit-compile eager per-op computations")
define_flag("allocator_strategy", "auto_growth", "kept for API parity; XLA owns HBM")
define_flag("use_autotune", False, "search + cache kernel tile sizes "
            "(reference: phi/kernels/autotune switch_autotune)")
define_flag("benchmark", False, "synchronize after every op (timing mode)")
define_flag("flash_block_q", 0,
            "override flash-attention q-block size (0 = default/autotune)")
define_flag("flash_block_k", 0,
            "override flash-attention k-block size (0 = default/autotune)")
define_flag("heter_max_payload_mb", 64,
            "cap (MiB) on a single array moved through the TCPStore by the "
            "heter gateway; large gradients belong on XLA collectives "
            "(reference rides Gloo here, ProcessGroupHeter.h:64)")
define_flag("heter_chunk_mb", 1,
            "chunk size (MiB) for store-routed heter payloads; 1 MiB "
            "fits the TCPStore client's probe buffer in one RPC")
define_flag("tracer_mkldnn_ops_on", "", "parity stub")
define_flag("max_inplace_grad_add", 0, "parity stub")
