import os

# The tests run on the single real CPU device, Pallas kernels in interpret
# mode (the 512-device flag is ONLY for the dry-run process — see
# src/repro/launch/dryrun.py). On a TPU the program is checked by
# chip_smoke.py at the repo root (``--chips 4`` for the slice path).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
