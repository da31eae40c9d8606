"""CPU rehearsal of the benchmark: run by hand, not by the repo's tier-1
suite (``testpaths`` there is ``tests/``)::

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
