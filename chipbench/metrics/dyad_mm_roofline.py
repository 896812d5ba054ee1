"""Share of its roofline that the DYAD projection kernels reach in the
training step: the least time of the step's DYAD forward, dgrad and wgrad
calls (counted by the configuration's model module), over the device time
of those kernels' events, summed over the train-step executions wholly
inside the traced window."""
from chipbench import work

# the Pallas calls of kernels/dyad_mm.py: the trace names each by the
# jitted function that holds it
KERNELS = ("%_mm_impl", "%_wgrad_impl")
STEP = "train_step"


def match(name: str) -> bool:
    return name.startswith(KERNELS)


def read(rec):
    red = rec.get("reduced")
    if rec["kind"] != "train" or red is None:
        return None
    steps = red.modules(lambda n: STEP in n)
    device_s = red.op_time_s(match, steps)
    if not steps or device_s <= 0:
        return None
    least = sum(work.least_time(f, b, rec["peaks"]) for f, b in
                rec["model"].train_dyad_mm_calls(rec["m"], rec["batch"],
                                                  rec["seq"]))
    return 100.0 * least * len(steps) / device_s
