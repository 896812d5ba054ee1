"""Share of the train step's leaf device time under none of the program's
layer scopes, in the train-step executions wholly inside the traced window
(chipbench/scopes.py): it rises when a refactor drops a scope."""
from chipbench import scopes


def read(rec):
    return scopes.unscoped_share(rec)
