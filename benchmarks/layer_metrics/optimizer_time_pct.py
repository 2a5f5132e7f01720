"""Share of the device's busy time spent under the named scope
``optimizer_step``: AdamW's update where it runs as operations of its
own (the embedding table, the output head, the norms).  XLA fuses a
matrix's update behind its gradient's matrix product; such a fusion is
read under the product's scope (``mlp``, ``attn_qkv``, ``attn_out``),
where its time goes, and is not counted here."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.scope_pct(run, "optimizer_step")
