"""The benchmark's tests of its fourth architecture, in tier-1 the way
tests/test_cellbench_dots3.py brings the third: the tiny `LongCat-Flash`
share run whole through `run_on_tpu` on the CPU (sound `correct: true`, the
int8 control `correct: false`), the cell's entries, the configuration's
widths, the traffic, the step's needs, the new reader."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cellbench", "tests"))

from cellbench.tests.test_longcat_flash import *  # noqa: E402,F401,F403
