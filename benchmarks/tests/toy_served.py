"""A served configuration of the tests' own: the program's Llama under
a model class, a witness function, a reference and cost counts that the
configuration file names (``rehearsal.add_witnessed_cells``).  A dense
model chooses nothing, so its witness carries what shows that the
plumbing is whole: the first layer's cached values of the checked
sequence, read back through the block table from the pools the step
programs wrote."""
import numpy as np

from paddle_tpu.models import LlamaForCausalLM


class ServedLM(LlamaForCausalLM):
    """The configuration's own model class."""


def witness(model, engine, tokens, block_table, prompt_tokens):
    values = np.asarray(engine.pool.layers[0][1])   # [blocks, size, KVH, D]
    size = values.shape[1]
    at = np.arange(len(tokens))
    return {"values": values[np.asarray(block_table)[at // size], at % size],
            "prompt_tokens": np.int32(prompt_tokens)}


def witness_of_an_empty_cache(model, engine, tokens, block_table,
                              prompt_tokens):
    """The fault: what is handed over is not what the steps wrote."""
    w = witness(model, engine, tokens, block_table, prompt_tokens)
    return dict(w, values=np.zeros_like(w["values"]))
