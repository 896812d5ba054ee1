"""Share of its roofline that the paged decode attention kernel reaches:
the least time of every live lane's query against its whole cached
context, per layer, in every engine step wholly inside the traced window
(counted by the configuration's model module), over the device time of
the kernel's events in those steps."""
from chipbench import work

KERNELS = ("%_decode_paged_impl",)


def match(name: str) -> bool:
    return name.startswith(KERNELS)


def read(rec):
    red = rec.get("reduced")
    if rec["kind"] != "serve" or red is None:
        return None
    by_i = {s["i"]: s for s in rec["steps"]}
    spans, least = [], 0.0
    for _, start, dur, args in red.host("step"):
        s = by_i.get(int(args.get("step", -1)))
        if s is None:
            continue
        spans.append((start, start + dur))
        if s["contexts"]:
            least += sum(work.least_time(f, b, rec["peaks"]) for f, b in
                         rec["model"].paged_decode_calls(rec["m"],
                                                         s["contexts"]))
    device_s = red.op_time_s(match, spans)
    if not spans or device_s <= 0:
        return None
    return 100.0 * least / device_s
