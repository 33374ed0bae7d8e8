"""Traversal kernels' (edge softmax, aggregation) share of their roofline
(%), full-graph training: least time of the window's steps
(``bench/work.py``) over the device time of the kernels
``kernel_names.json`` lists under ``traversal``."""
import json
import pathlib

from bench import work

_NAMES = json.loads(pathlib.Path(__file__).with_name(
    "kernel_names.json").read_text())


def read(data):
    return work.roofline_share(data, "traversal", _NAMES["traversal"])
