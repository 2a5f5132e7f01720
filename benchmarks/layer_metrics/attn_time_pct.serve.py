"""Share of the device's busy time spent under the named scope ``attn``
of every step program: the paged decode kernel's and the chunk kernel's
walks (a window layer's start at the window's first page) with the
write of the new keys and values beside them."""
from benchmarks.harness import program_trace


def read(run):
    return program_trace.scope_pct(run, "attn")
