"""The benchmark's window arithmetic and the traffic generator's fixed order,
under every PR's tests: the cases live beside the code they pin."""

from benchmarks.tests.test_bench_window import *  # noqa
