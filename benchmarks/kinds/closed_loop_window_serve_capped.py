"""``closed_loop_window_serve`` (imported, not copied) with the check's
decode row as long as the engine admits: ``check_decode_steps`` of the
mix, at most half of the engine's ``max_model_len``, and the prompt of
the row cut to leave room for them.

Why a kind of its own: a decode row LONGER THAN THE WINDOW is the point
of such a check (by its last step every key a window layer reads was
written by the decode program, which a row of a few steps cannot show),
and a mix that asks for 160 steps must still run on an engine whose
``max_model_len`` is smaller than that (the CPU rehearsal's tiny
configurations).  At the cell's own sizes the cap does nothing: every
number the check compares and reports is ``closed_loop_window_serve``'s.
"""
from __future__ import annotations

import dataclasses

from benchmarks.kinds import closed_loop_window_serve as window_serve


def run(ctx) -> dict:
    config, mix = ctx.cell.config, ctx.cell.traffic
    limit = min(config["serving"].get("max_model_len")
                or config["max_position_embeddings"],
                config["max_position_embeddings"])
    steps = min(mix.get("check_decode_steps",
                        window_serve.CHECK_DECODE_STEPS), limit // 2)
    cell = dataclasses.replace(ctx.cell,
                               traffic=dict(mix, check_decode_steps=steps))
    return window_serve.run(dataclasses.replace(ctx, cell=cell))
