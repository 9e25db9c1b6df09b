"""What is left of the run-time block autotuner, for two callers this
repo may not edit yet: `benchmark/harness.py:121-126` prints `tuned_log()`
on its NOTES line and `benchmark/tests/test_harness.py:104-111` calls
`reset_for_tests()`. Nothing else imports this module; it goes when they do
(PERF.md, "For the next `benchmark` issue")."""
from . import tiling as _tiling


def tuned_log() -> list:
    return []


def reset_for_tests():
    _tiling.reset_compile_checks()
