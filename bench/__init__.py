"""The on-chip benchmark: named cells of a configuration under a traffic
mix, run one at a time by ``python3 -m bench.run`` (see ``bench/run.py``
and ``BENCHMARK.json``)."""
