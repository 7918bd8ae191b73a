"""On-chip benchmark of the serving and training paths (``bench/run.py``)."""
