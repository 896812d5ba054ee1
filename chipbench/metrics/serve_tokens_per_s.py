"""Output tokens emitted in the window over the window."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    w0, w1 = rec["w0"], rec["w1"]
    n = sum(1 for r in rec["reqs"].values() for t in r["times"]
            if w0 <= t <= w1)
    return n / (w1 - w0)
