"""The benchmark's own tests run on the CPU, at small sizes:

    python -m pytest bench/tests

They put the checkout and its ``src`` on the path, keep JAX on the CPU and
its persistent compile cache off."""
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
