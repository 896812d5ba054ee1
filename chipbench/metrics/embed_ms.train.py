"""Device milliseconds per train step under the program's ``embed`` scope (the
one-hot token embedding and the learned positions), forward and backward, in
the train-step executions wholly inside the traced window
(chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.ms(rec, "embed")
