"""The benchmark's own tests run on the CPU, from the repository root:

    python -m pytest -q bench/tests

Pallas kernels run in interpret mode there, so the rehearsals use tiny
eval sets."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
