"""The benchmark's own tests run on the CPU: `python -m pytest benchmark/tests`."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))
