"""Unattributed device time per full-graph training step (ms): the step's
instructions that no IR op, loss or optimizer owns, such as copies XLA
inserts (``bench/device_owners.py``)."""
from bench import device_owners as D


def read(data):
    return D.ms_per_step(data, lambda o: o is None)
