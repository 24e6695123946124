"""chipbench — the on-chip benchmark of the served verify path.

Everything the yardstick needs lives here (traffic, data, the plain
reference, the reduction from spans/counters/trace to metrics, peaks);
from the program it takes only the system under test and its spans,
counters and kernel names.  See README.md.
"""
