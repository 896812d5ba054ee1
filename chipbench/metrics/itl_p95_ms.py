"""95th percentile over every gap between consecutive output tokens of
every request, for the gaps that end in the window.  A token is timed as
the engine step that emitted it returns."""
from chipbench import common


def read(rec):
    if rec["kind"] != "serve":
        return None
    w0, w1 = rec["w0"], rec["w1"]
    gaps = [b - a for r in rec["reqs"].values()
            for a, b in zip(r["times"], r["times"][1:]) if w0 <= b <= w1]
    return 1e3 * common.percentile(gaps, 95) if gaps else None
