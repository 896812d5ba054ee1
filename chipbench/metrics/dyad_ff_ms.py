"""Device milliseconds of the DYAD ff megakernel per engine step: the
device time of its events in the engine steps wholly inside the traced
window, over the number of those steps.  (Its share of a roofline cannot
be read from its own events: XLA stages its bf16 weight operands into
on-chip memory with asynchronous copies outside the kernel, so the
kernel's events run faster than reading the weights from HBM would
allow.)"""
# the Pallas call of kernels/dyad_mm.py: the trace names it by the jitted
# function that holds it
KERNELS = ("%_ff_impl",)


def match(name: str) -> bool:
    return name.startswith(KERNELS)


def read(rec):
    red = rec.get("reduced")
    if rec["kind"] != "serve" or red is None:
        return None
    spans = [(start, start + dur) for _, start, dur, _ in red.host("step")]
    device_s = red.op_time_s(match, spans)
    if not spans or device_s <= 0:
        return None
    return 1e3 * device_s / len(spans)
