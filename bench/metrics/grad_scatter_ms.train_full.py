"""Gradient scatters per full-graph training step (ms): device time of the
backward pass's scatter-adds, owned by an IR op or the loss. Each is the
transpose of a forward row gather (messages, features, the un-padding of
a GEMM's rows, the loss rows), or in a gather-fused kernel's backward the
scatter written there (``kernels/ops.py``) (``bench/device_owners.py``)."""
from bench import device_owners as D


def read(data):
    return D.ms_per_step(data, lambda o: D.model(o)
                         and o.direction == "backward"
                         and o.inner.split("/")[-1] == "scatter-add")
