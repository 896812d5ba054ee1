"""Device time of the train step under a scope nested inside one of the
program's layer scopes (``router``, ``experts`` inside ``ff``; ``latent``
inside ``attn``), read like chipbench/scopes.py reads the layer scopes: a
leaf operation counts when the name is a component of its ``op_name``
path, over the train-step executions wholly inside the traced window."""
from __future__ import annotations

from chipbench import scopes, tracing


def has_scope(op_name, name: str) -> bool:
    """``name`` is a component of ``op_name``'s path (a transform around
    it, ``transpose(jvp(router))``, counts as it)."""
    return any(scopes._TRANSFORM.sub("", part).rstrip(")") == name
               for part in (op_name or "").split("/"))


def ms(rec: dict, name: str):
    """Device milliseconds per train step under scope ``name``; None when
    the trace holds no whole step or no operation under it (a program
    without the scope)."""
    if scopes.per_step_s(rec) is None:
        return None
    red, names = rec["reduced"], rec["op_names"]
    steps = scopes.whole_steps(red)
    total, found = 0.0, False
    for d in red.devices:
        for ev in tracing.leaves(d["ops"]):
            if not has_scope(names.get(scopes.instruction(ev[0])), name):
                continue
            for lo, hi in steps:
                c = tracing.clip(ev, lo, hi)
                if c is not None:
                    total += c[1] - c[0]
                    found = True
    if not found:
        return None
    return 1e-6 * total / len(red.devices) / len(steps)
