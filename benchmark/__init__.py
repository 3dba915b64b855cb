"""The benchmark of open-simulator-tpu (see BENCHMARK.json and PERF.md).

Everything here is the yardstick: traffic generation, the plain
reference scheduler, the reduction from traces to metrics and the
check that decides ``correct``. It imports the program only as the
system under test.
"""
