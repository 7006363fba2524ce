"""The chip benchmark of the clustering system: cells of a configuration
and a traffic mix, found by name from ``BENCHMARK.json`` (``run.py``)."""
