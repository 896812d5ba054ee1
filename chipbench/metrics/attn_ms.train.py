"""Device milliseconds per train step under the program's ``attn`` scope (the
q/k/v/o projections, head reshapes, the flash kernels and the residual add),
forward and backward, in the train-step executions wholly inside the traced
window (chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.ms(rec, "attn")
