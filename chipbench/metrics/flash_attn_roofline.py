"""Share of its roofline that the flash attention kernels reach in the
training step: the least time of the step's causal attention forward and
backward (counted by the configuration's model module), over the device
time of the prefill, dq and dkv kernels' events, summed over the
train-step executions wholly inside the traced window."""
from chipbench import work

# the Pallas calls of kernels/flash_attn.py: the trace names each by the
# jitted function that holds it
KERNELS = ("%_prefill_impl", "%_dq_impl", "%_dkv_impl")
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
                rec["model"].train_flash_calls(rec["m"], rec["batch"],
                                                rec["seq"]))
    return 100.0 * least * len(steps) / device_s
