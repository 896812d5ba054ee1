"""Device milliseconds per train step under the program's ``optimizer`` scope
(the gradient-norm clip, AdamW and the non-finite guard), forward and
backward, in the train-step executions wholly inside the traced window
(chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.ms(rec, "optimizer")
