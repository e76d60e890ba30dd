"""The chip benchmark of the served tree path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix, row generator or metric lives in a file of its
own under this directory and is found by the name ``BENCHMARK.json`` gives
it (see :mod:`bench.catalog`).
"""
