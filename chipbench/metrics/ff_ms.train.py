"""Device milliseconds per train step under the program's ``ff`` scope (the
DYAD kernels, the block-major swaps around them, the activation and the
residual add), forward and backward, in the train-step executions wholly
inside the traced window (chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.ms(rec, "ff")
