import os
import pathlib
import sys

# The harness's tests run on the CPU (rehearsals, arithmetic, trace files).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = pathlib.Path(__file__).resolve().parents[2]
for _p in (_REPO, _REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
