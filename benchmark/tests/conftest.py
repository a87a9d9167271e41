"""The benchmark's own tests run on the CPU with four virtual devices:

    python -m pytest benchmark/tests -q        (from the root of the repo)

They rehearse control flow and arithmetic; no number they see is a device
number.  Tier-1 (``tests/``) does not collect this directory.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
