"""The benchmark's tests of its sixth architecture, in tier-1 the way
tests/test_cellbench_laguna.py brings the fifth: the tiny `deepseek_v32`
share run whole through `run_on_tpu` on the CPU (sound `correct: true`, the
int8 control and an altered token `correct: false`), the cell's entries
standing together, the configuration's sizes against the catalog, the
traffic, the step's and the two-stage read's needs against a hand count, the
read's share of its roofline and the sort's width.

The tests of earlier cells that hold the benchmark to a number of cells or
hold entries "at the end of the list" (cellbench/tests/test_granite_hybrid.py,
test_readers_tracing.py, test_longcat_flash.py) stay met as PR 42 met them:
tier-1 runs their shadows in tests/test_cellbench_granite.py,
test_cellbench_readers.py and test_cellbench_longcat.py, which hold what
stays true when a cell is appended, and this cell is appended."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cellbench", "tests"))

from cellbench.tests.test_deepseek_v32 import *  # noqa: E402,F401,F403
