"""The benchmark's own tests, under the tier-1 gate (they live with the
benchmark, in ``benchmarks/tests/``)."""
from benchmarks.tests.test_benchmark_harness import *  # noqa: F401,F403
from benchmarks.tests.test_layer_readers import *  # noqa: F401,F403
from benchmarks.tests.test_hybrid_cell import *  # noqa: F401,F403
