"""The benchmark of bluefog_tpu: one cell (a model configuration under one
traffic mix) per run of ``python benchmark/run.py``. BENCHMARK.json at the
root of the checkout names every cell, configuration and metric; every other
file here is found by one of those names (see ``manifest.py``)."""
