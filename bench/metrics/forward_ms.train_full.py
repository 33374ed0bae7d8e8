"""Forward pass per full-graph training step (ms): device time of the step's
instructions owned by an IR op or the loss, forward direction
(``bench/device_owners.py``)."""
from bench import device_owners as D


def read(data):
    return D.ms_per_step(data, lambda o: D.model(o)
                         and o.direction == "forward")
