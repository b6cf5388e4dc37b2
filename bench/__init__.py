"""The chip benchmark of the EnGN reproduction (see bench/run.py)."""
