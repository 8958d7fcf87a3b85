import os
import tempfile
import sys
from pathlib import Path

# the harness's tests run on the CPU, with the program's jnp oracle
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU programs of this process only: nothing shared with the chip's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chipbench-tests-"))
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
