"""The on-chip benchmark of the smoother service (see ``run.py``)."""
