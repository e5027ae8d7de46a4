"""CPU rehearsal of the chip benchmark at toy sizes.

Eight host devices, set before JAX is imported, as the repository's own
test configuration sets them (the two must agree: pytest imports both).
"""
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
