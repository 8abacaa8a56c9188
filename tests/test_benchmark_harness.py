"""The benchmark harness's own tests, collected into tier-1.

``benchmarks/`` is the yardstick every PR is judged by, and a PR may not
edit it; its tests (``benchmarks/tests/``: the reducers' arithmetic on
hand-built traces and a capture recorded on the chip, and ``run.py
--rehearse`` end to end on the CPU at toy widths) sit outside
``testpaths``.  This module imports them so the tier-1 command runs them
unchanged: ``benchmarks`` is a namespace package importable from the repo
root, and ``test_rehearse.py`` finds its files from its own ``__file__``.
"""

from benchmarks.tests.test_reduce import *  # noqa: F403
from benchmarks.tests.test_rehearse import *  # noqa: F403
