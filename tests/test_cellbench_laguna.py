"""The benchmark's tests of its fifth architecture, in tier-1 the way
tests/test_cellbench_longcat.py brings the fourth: the tiny `laguna` stage run
whole through `run_on_tpu` on the CPU (sound `correct: true`, the int8 control
and an altered token `correct: false`), the cell's entries standing together,
the configuration's sizes against the catalog, the traffic, the step's needs
against a hand count, the kernel's share."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cellbench", "tests"))

from cellbench.tests.test_laguna import *  # noqa: E402,F401,F403
