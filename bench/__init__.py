"""The chip benchmark of the Hyft system: ``python bench/run.py`` (see
``BENCHMARK.json`` at the root of the repository)."""
