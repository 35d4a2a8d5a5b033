"""perfbench: the benchmark of sutro-tpu (BENCHMARK.json, PERF.md).

Everything the yardstick needs lives in this directory; from the program
it takes only the system under test (see ``sut.py``). ``README.md`` says
how to add a configuration, a traffic mix, a generator or a metric as
new files only.
"""
