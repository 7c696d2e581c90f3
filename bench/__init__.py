"""The on-chip benchmark: one command runs one cell (``bench/run.py``)."""
