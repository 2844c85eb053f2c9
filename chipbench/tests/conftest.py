"""The benchmark's CPU tests: JAX held to the CPU, the repository's source
and the benchmark importable."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
