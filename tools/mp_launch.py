#!/usr/bin/env python
"""Launch N emulated CPU cluster processes of a script.

    python tools/mp_launch.py -n 2 examples/pretrain_llama.py --steps 2

Each child gets JAX_PLATFORMS=cpu, forced host devices, and the
PADDLE_TPU_* coordinator triple; the script joins the cluster by calling
paddle_tpu.distributed.bootstrap.initialize_cluster() (no arguments).
The first child to die takes the job with it (fleet-controller
semantics); the launcher's exit code is 0 only if every process exits 0.

CPU-only by design: a chip belongs to one process, and one process drives
all the chips of a host, so nothing here ever starts a child on a chip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.distributed.bootstrap import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
