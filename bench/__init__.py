"""The chip benchmark of the serving path (``bench/run.py``)."""
