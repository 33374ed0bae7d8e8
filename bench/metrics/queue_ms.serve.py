"""Queueing in the serving runtime (ms): the mean ``Response.queue_ms``
(arrival to batch admission) of the window's completed requests."""


def read(data):
    q = data.get("queue_ms") or []
    return sum(q) / len(q) if q else None
