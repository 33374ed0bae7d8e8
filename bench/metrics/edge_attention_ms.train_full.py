"""Edge attention per full-graph training step (ms): device time of the
step's instructions owned by a traversal op (scope kind ``traversal``: the
edge scores, edge softmax and attention-weighted aggregation), forward and
backward (``bench/device_owners.py``)."""
from bench import device_owners as D


def read(data):
    return D.ms_per_step(data, lambda o: o is not None
                         and o.owner.split(".")[1:2] == ["traversal"])
