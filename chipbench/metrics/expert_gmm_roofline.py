"""Share of its roofline that the grouped DYAD expert kernels reach in the
training step: the least time of the step's grouped forward, dgrad and
wgrad calls over the rows routed to the held experts (counted by the
configuration's model module at their expectation), over the device time
of those kernels' events, summed over the train-step executions wholly
inside the traced window (without the stub of the one the profiler cut
short: ``scopes.whole_steps``)."""
from chipbench import scopes, work

# the Pallas calls of kernels/dyad_gmm.py: the trace names each by the
# jitted function that holds it
KERNELS = ("%_gmm_impl", "%_gmm_wgrad_impl")


def match(name: str) -> bool:
    return name.startswith(KERNELS)


def read(rec):
    red = rec.get("reduced")
    counts = getattr(rec["model"], "train_expert_gmm_calls", None)
    if rec["kind"] != "train" or red is None or counts is None:
        return None
    steps = scopes.whole_steps(red)
    device_s = red.op_time_s(match, steps)
    if not steps or device_s <= 0:
        return None
    least = sum(work.least_time(f, b, rec["peaks"])
                for f, b in counts(rec["m"], rec["batch"], rec["seq"]))
    return 100.0 * least * len(steps) / device_s
