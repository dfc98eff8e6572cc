"""The control of `correct`, through the harness: one run of a cell as the
command makes it, except that where the served tokens are compared with the
reference, the first choices of the reference in the lower precision that
the configuration's `check.control` names stand in their place (at the same
positions of the same prompts and served tokens). The run has to come out
`correct: false`. On the chip, at a cell's own size:

    python3 cellbench/tests/control_run.py --workload <name> --seed <n> --seconds <s>

The benchmark's own runs never pass through here.
"""

from __future__ import annotations

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import run, serve  # noqa: E402

sound_check = serve.run_check


def control_check(cell: dict, samples: list) -> dict:
    return sound_check(cell, samples, lower=cell["config"]["check"]["control"])


if __name__ == "__main__":
    serve.run_check = control_check
    sys.exit(run.main())
