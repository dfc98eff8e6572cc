"""The benchmark's own fast tests, in tier-1: the readers of what the
program says about its time (cellbench/tests/test_readers_tracing.py), the
contract's names and arrows with the entries this repo has, the trace
reduction and the traffic generator. The whole runs through `run_on_tpu`
(cellbench/tests/test_control.py, test_broken_path.py; minutes) stay out:
`python -m pytest cellbench/tests` runs them."""

from cellbench.tests.test_names import *  # noqa: F401,F403
from cellbench.tests.test_readers_tracing import *  # noqa: F401,F403
from cellbench.tests.test_trace import *  # noqa: F401,F403
from cellbench.tests.test_traffic import *  # noqa: F401,F403
