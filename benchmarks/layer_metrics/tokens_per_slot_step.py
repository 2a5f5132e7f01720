"""Tokens a live slot finalises per run of the block program:
``tokens_unmasked`` over ``block_slot_steps`` (commit passes counted).
1.0 is what an autoregressive step yields; a block of L positions
denoised in T steps and committed in a step of its own yields
L / (T + 1)."""


def read(run):
    counters = run.get("counters") or {}
    slot_steps = counters.get("block_slot_steps")
    if not slot_steps:
        return None
    return counters["tokens_unmasked"] / slot_steps
