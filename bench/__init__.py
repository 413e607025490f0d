"""Chip benchmark of one WWW.Serve serving node (see ``bench/run.py``)."""
