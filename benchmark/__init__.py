"""The on-chip benchmark of the volunteer work loop (see ``run.py``)."""
