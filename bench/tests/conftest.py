"""The benchmark's tests run on the CPU at small sizes: the system under
test (``src/``), the repository root (the ``bench`` package) and the
ifunc libraries are put on the path here."""

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
os.environ.setdefault("REPRO_IFUNC_LIB_DIR", str(ROOT / "ifunc_libs"))
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
