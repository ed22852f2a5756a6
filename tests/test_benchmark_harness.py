"""Tier-1 counts the benchmark's own CPU tests: ``benchmarks/tests`` lies
outside ``tests/``, so its cases are imported here (the benchmark's
contract, generators, arithmetic and trace reductions on hand-made events,
the drivers on the rehearsal configurations with their planted faults, and
the span readers)."""

from benchmarks.tests.test_harness import *  # noqa: F401,F403
from benchmarks.tests.test_spans import *  # noqa: F401,F403
from benchmarks.tests.test_sessions import *  # noqa: F401,F403
from benchmarks.tests.test_looped import *  # noqa: F401,F403
from benchmarks.tests.test_rounds import *  # noqa: F401,F403
