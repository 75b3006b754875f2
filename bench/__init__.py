"""The on-chip benchmark of the served fraud-detection path (see
``bench/run.py`` and ``BENCHMARK.json``)."""
