"""Kernel tile-size autotuner with a persistent disk cache.

TPU-native analog of the reference kernel autotune machinery
(/root/reference/paddle/phi/kernels/autotune/cache.h AutoTuneCache and
switch_autotune.h): candidate tile configs are timed once on the real
device, and the winner is cached keyed on (op, shape signature, dtype) —
in memory for the process and as JSON on disk across processes.

Gated by FLAGS_use_autotune (core/flags); without it callers use their
static defaults and never pay the search.

Cache keys are CHIP-QUALIFIED: the same op/shape tunes differently on
v5e vs v6e vs the CPU fallback, so the accelerator kind is stamped
into every key.  PADDLE_TPU_RETUNE=1 (or ``set_retune``) is the
escape hatch: cached winners are ignored and re-measured once, then
the fresh result overwrites the disk cache.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

_mem_cache: Dict[str, Any] = {}
_disk_loaded = False
_dirty = False
_chip_name: Optional[str] = None
_retune = False


def _chip() -> str:
    """Accelerator kind for the cache key (e.g. ``TPU_v5_lite`` or
    ``cpu``) — resolved once; device enumeration is not free.  A
    backend that cannot say what it is raises: a winner filed under a
    made-up chip name would be served to every chip."""
    global _chip_name
    if _chip_name is None:
        import jax

        kind = jax.devices()[0].device_kind
        _chip_name = str(kind).strip().replace(" ", "_") or \
            jax.default_backend()
    return _chip_name


def set_retune(enabled: bool):
    """Ignore cached winners and re-measure."""
    global _retune
    _retune = bool(enabled)


def retune_enabled() -> bool:
    return _retune or os.environ.get("PADDLE_TPU_RETUNE", "") in (
        "1", "true", "True")


def _cache_path() -> str:
    # next to the compile cache: a home directory does not outlive a
    # chip call, and tiles without their compiled programs save nothing
    from ..core.compile_cache import cache_dir

    base = os.environ.get("PADDLE_TPU_CACHE_DIR") or cache_dir()
    return os.path.join(base, "autotune.json")


def _load_disk():
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        with open(_cache_path()) as f:
            disk = json.load(f)
        for k, v in disk.items():
            _mem_cache.setdefault(k, v)
    except Exception:
        pass


def _save_disk():
    global _dirty
    if not _dirty:
        return
    try:
        path = _cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # atomic publish (resilience tmp+fsync+rename idiom): a reader
        # racing this write sees either the old cache or the new one,
        # never a torn JSON file
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(_mem_cache, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _dirty = False
    except Exception:
        pass


def cache_key(op: str, *parts) -> str:
    """(chip, op, shape-key) — the chip prefix keeps one shared disk
    cache correct across accelerator generations."""
    return f"{_chip()}|{op}|" + "|".join(str(p) for p in parts)


def autotune(op: str, key_parts: Iterable,
             candidates: Iterable[Tuple],
             run_fn: Callable[[Tuple], Any],
             warmup: int = 1, iters: int = 3) -> Optional[Tuple]:
    """Return the fastest candidate config for this key.

    run_fn(config) must execute the kernel end-to-end and block until the
    result is ready.  Configs that raise are skipped.  The winner persists
    to disk; subsequent processes skip the search entirely.
    """
    global _dirty
    _load_disk()
    key = cache_key(op, *key_parts)
    hit = _mem_cache.get(key)
    if hit is not None and not retune_enabled():
        return tuple(hit)

    best, best_t = None, float("inf")
    for cfg in candidates:
        try:
            for _ in range(warmup):
                run_fn(cfg)
            t0 = time.perf_counter()
            for _ in range(iters):
                run_fn(cfg)
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue
        if dt < best_t:
            best, best_t = cfg, dt
    if best is not None:
        _mem_cache[key] = list(best)
        _dirty = True
        _save_disk()
    return best


def lookup(op: str, key_parts: Iterable) -> Optional[Tuple]:
    """Cache-only probe (no search) — safe under a jit trace, where timing
    is impossible but shapes are static so prior results still apply."""
    _load_disk()
    hit = _mem_cache.get(cache_key(op, *key_parts))
    return tuple(hit) if hit is not None else None


def clear(disk: bool = False):
    _mem_cache.clear()
    if disk:
        try:
            os.remove(_cache_path())
        except OSError:
            pass
