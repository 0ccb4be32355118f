"""The benchmark of the served solve path (see ``bench/run.py``)."""
