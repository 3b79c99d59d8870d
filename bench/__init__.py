"""On-chip benchmark of the AnycostFL round loop (see ``bench/run.py``)."""
