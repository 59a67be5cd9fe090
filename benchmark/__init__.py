"""The benchmark: cells of one configuration under one traffic mix,
run one at a time by ``benchmark/run.py``.  See README.md here."""
