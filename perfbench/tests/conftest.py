"""The benchmark's own tests: ``python -m pytest perfbench/tests`` from the
checkout's root, on the CPU (JAX_PLATFORMS=cpu)."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[1] / "src"), str(HERE.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
