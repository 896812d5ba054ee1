"""Device milliseconds per train step under the program's ``experts`` scope
inside ``ff`` (the held experts: the dispatch into sorted rows, the
grouped DYAD kernels and the layout swaps around them, the SwiGLU),
forward and backward, in the train-step executions wholly inside the
traced window (chipbench/subscopes.py)."""
from chipbench import subscopes


def read(rec):
    return subscopes.ms(rec, "experts")
