"""Chip benchmark of the Elastic Net solver; see `BENCHMARK.json` and
`bench/run.py`."""
