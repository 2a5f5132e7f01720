"""Share of the live slot-steps of the block program that were commit
passes (``commit_slot_steps`` / ``block_slot_steps``): a forward that
finalises nothing, run to write a finished block's keys and values."""


def read(run):
    counters = run.get("counters") or {}
    slot_steps = counters.get("block_slot_steps")
    if not slot_steps:
        return None
    return 100.0 * counters["commit_slot_steps"] / slot_steps
