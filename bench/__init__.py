"""Chip benchmark of the served WLSH retrieval path.

One run drives one cell of ``BENCHMARK.json`` (a deployment under a
traffic mix) through the real-time serving stack and prints one JSON
result line; see ``bench/run.py``.  Everything that decides a number,
from traffic generation to the reference that decides ``correct``, lives
in this package so that a change to the program cannot move it.
"""
