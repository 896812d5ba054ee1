"""95th percentile, over every request due in the window, of its first
token's time minus the time it was due.  A request that never produced a
token counts with the wait it had when the run stopped looking."""
from chipbench import common


def read(rec):
    if rec["kind"] != "serve":
        return None
    w0, w1 = rec["w0"], rec["w1"]
    ttft = [(r["times"][0] if r["times"] else rec["t_end"]) - r["due"]
            for r in rec["reqs"].values() if w0 <= r["due"] <= w1]
    return 1e3 * common.percentile(ttft, 95) if ttft else None
