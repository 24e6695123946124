"""The harness's own tests: `pytest chipbench/tests` from the repo root, by
hand — they are not part of tier-1 (tests/) and need no chip.  The ones
that drive a run do it on XLA-CPU at the configurations' rehearse sizes;
the first of them traces and compiles one verify program (~2 min)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
