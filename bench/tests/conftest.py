"""Sizes the tiny copy of the benchmark (``tiny.py``) gives each cell
added after it, so the CPU tests that drive every cell of
``BENCHMARK.json`` (``test_bench_run.py``) run it in seconds."""
from bench.tests import tiny

#: the Pegasus DAGs at 24 tasks (the smallest Montage is 11) on 4
#: machines, 30 replicas a call (three paired cells) in chunks of 15
tiny.CONFIGS.setdefault("pegasus1000x16", dict(n_tasks=24, n_machines=4,
                                               n_machine_types=4))
tiny.TRAFFIC.setdefault("dag", dict(replicas=30, chunk=15))
