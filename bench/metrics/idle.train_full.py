"""Device idle share (%) of the traced window: 1 - the union of device-op
intervals over the window (``bench/trace_reduce.py``)."""


def read(data):
    trace = data["trace"]
    if trace.window_s <= 0:
        return None
    return 100.0 * trace.idle_share
