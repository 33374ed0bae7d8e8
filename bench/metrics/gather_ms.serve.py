"""Feature gather per served batch (ms): the mean of the program's
``feature_gather`` spans over the window."""


def read(data):
    durs = [s["dur"] for s in data.get("spans") or []
            if s["name"] == "feature_gather"]
    return sum(durs) / len(durs) / 1e3 if durs else None
