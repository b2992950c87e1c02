"""The on-chip benchmark of the secure consortium fit (see ``run.py``)."""
