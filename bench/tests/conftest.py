"""The benchmark's own tests: CPU only, tiny sizes, Pallas kernels interpreted.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They are among the repository's ``testpaths``, so its test run collects
them too.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
