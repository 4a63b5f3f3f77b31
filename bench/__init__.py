"""Chip benchmark of the window-analytics service (see ``bench/run.py``)."""
