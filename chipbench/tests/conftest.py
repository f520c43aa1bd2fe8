"""The benchmark's own tests: CPU only, run by hand in rehearsal
(``CHIPBENCH_REHEARSAL=1 JAX_PLATFORMS=cpu python -m pytest chipbench/tests``).
They are not part of the repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("CHIPBENCH_REHEARSAL", "1")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
