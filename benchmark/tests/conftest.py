"""The harness's own tests run on the CPU at a tiny size: they check control
flow, the comparison and the reductions, never a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the program's own switch: no writes to the persistent cache from tests
os.environ.setdefault("PIO_XLA_CACHE", "off")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
