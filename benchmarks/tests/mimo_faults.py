"""Faults planted into the program that serves a model with window and
full layers of different head counts and a sink in the window layers'
softmax, each as ``fault(setattr)`` with ``setattr(object, name, value)``
(a test's ``monkeypatch.setattr``, or ``window_faults.planted``, which
undoes them).  ``test_mimo_cell.py`` plants them at tiny widths on the
CPU; ``mimo_controls.py`` plants them at the cell's own sizes on the
chip.  Every one must turn the cell's ``correct`` false."""
from __future__ import annotations

from benchmarks.tests.window_faults import (  # noqa: F401  (shared faults)
    planted, reference_weights_in, window_layers_run_as_full_layers)


def the_sink_left_out(setattr_):
    """Every window layer's softmax without its sink logit, in every
    program."""
    from paddle_tpu.models import mimo_v2

    setattr_(mimo_v2, "_sink", lambda layer: None)


def window_keys_unrotated_in_the_decode_program(setattr_):
    """The decode program writes a window layer's new key as it came,
    unrotated (its query is turned as ever).  The prompt's keys are
    sound, so the first decode steps read one such key of 128 a window
    layer; once the window has moved past the prompt every key it reads
    is one: a fault that shows in the late rows of a long decode check."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_attention as pa

    decode = pa.fused_paged_decode

    def unrotated(q, k_new, v_new, k_pool, v_pool, block_table, positions,
                  cos, sin, *, window=None, rotary=None, **kw):
        if window is not None and rotary is not None:
            # turned back by the angle the prologue turns it by
            at = jnp.asarray(positions, jnp.int32)
            k_new = pa._rotate_lanes(
                k_new.astype(jnp.float32), cos[at][:, None, None, :],
                -sin[at][:, None, None, :], rotary).astype(k_new.dtype)
        return decode(q, k_new, v_new, k_pool, v_pool, block_table,
                      positions, cos, sin, window=window, rotary=rotary,
                      **kw)

    setattr_(pa, "fused_paged_decode", unrotated)
