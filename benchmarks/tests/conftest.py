"""The benchmark's own tests: CPU only, tiny sizes, data under `data/`.

    python -m pytest benchmarks/tests -q

Not part of the repo's tier-1 suite (`tests/`), which this PR leaves alone.
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the four-chip cell's mesh
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
