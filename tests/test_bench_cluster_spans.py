"""The benchmark's readers of the run's cluster trace (``spans.jsonl``:
``benchmarks/lib/cluster_spans.py`` and the two readers built on it), under
every PR's tests: the cases live beside the code they pin."""

from benchmarks.tests.test_bench_cluster_spans import *  # noqa
