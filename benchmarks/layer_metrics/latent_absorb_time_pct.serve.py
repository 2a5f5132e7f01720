"""Share of the device's busy time spent under the named scope
``attn_absorb`` of every step program: carrying every head's query into
the latent space (``q_nope . W_UK^T``) and the attended latent back out
(``o~ . W_UV``), what the absorbed form pays in every step for reading a
cached page once and never writing a head's keys or values out."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.scope_pct(run, "attn_absorb")
