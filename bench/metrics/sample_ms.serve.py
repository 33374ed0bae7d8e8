"""Host sampler and layout build per served batch (ms): the mean, over the
batches of the window, of the program's ``sample`` plus ``layout`` spans."""


def read(data):
    spans = data.get("spans") or []
    n = sum(1 for s in spans if s["name"] == "sample")
    if n == 0:
        return None
    total_us = sum(s["dur"] for s in spans
                   if s["name"] in ("sample", "layout"))
    return total_us / n / 1e3
