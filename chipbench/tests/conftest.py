import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parent))

# the benchmark's tests run on the CPU and write no compile cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
