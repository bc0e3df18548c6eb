import os

# CPU only, with the same 8 virtual devices as the repository's own
# tests, whichever conftest a test process imports first
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the program under test, as bench/run.py finds it
_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
