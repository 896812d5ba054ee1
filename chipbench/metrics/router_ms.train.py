"""Device milliseconds per train step under the program's ``router`` scope
inside ``ff`` (the expert layer's scores, top-k selection, the sorted-row
layout and the combine), forward and backward, in the train-step
executions wholly inside the traced window (chipbench/subscopes.py)."""
from chipbench import subscopes


def read(rec):
    return subscopes.ms(rec, "router")
