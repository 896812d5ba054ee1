"""Device time of the train step split by the program's layer scopes.

The program wraps each layer in one ``jax.named_scope`` (``embed``,
``norm``, ``attn``, ``ff``, ``head``, ``optimizer``), and the scope path
lands in the ``op_name`` of every HLO instruction, forward, backward and
remat alike (``jit(train_step)/transpose(jvp(ff))/...``).  A device
operation goes under the first (outermost) of those names in its path, or
under ``unscoped``.

The trace names a device operation by its HLO instruction
(``%fusion.3 = ...``) and carries no ``op_name``; :func:`op_names` maps
instruction names to ``op_name`` from the step's optimized HLO text, which
:func:`step_hlo` compiles again after the window (the compile cache serves
it).  An instruction that carries no ``op_name`` of its own (XLA gives
some fusions none) takes the one of the computation it calls: its root's,
else the first one there.

For the train-step executions wholly inside the traced window, the device
time of each leaf operation (a loop is not counted again over its body) is
summed per scope and divided by the number of steps.  When the profiler
stops, it records the execution in flight as a stub of well under a
millisecond that can still lie inside the window; a step takes hundreds,
so executions shorter than half the median are not counted as steps.
"""
from __future__ import annotations

import argparse
import collections
import re
import time

from chipbench import common, tracing

SCOPES = ("embed", "norm", "attn", "ff", "head", "optimizer")
UNSCOPED = "unscoped"
STEP = "train_step"

_HEADER = re.compile(r"(?:ENTRY )?%?([^\s(]+) .*\{\s*$")
_INSTR = re.compile(r"\s+(ROOT )?%([^\s=]+) = .*?\s([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,]+)")
_TRANSFORM = re.compile(r"^(?:[\w-]+\()+")


def scope_of(op_name) -> str:
    """The first of :data:`SCOPES` among the components of ``op_name``'s
    path (a transform around a scope, ``transpose(jvp(ff))``, counts as
    the scope), else :data:`UNSCOPED`."""
    for part in (op_name or "").split("/"):
        name = _TRANSFORM.sub("", part).rstrip(")")
        if name in SCOPES:
            return name
    return UNSCOPED


def parse_hlo(hlo_text: str) -> dict:
    """``{computation: [(name, opcode, op_name or None, [called
    computations], is_root)]}`` of an optimized HLO module's text."""
    comps, comp = {}, None
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = _HEADER.match(line)
            comp = comps.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        op = _OP_NAME.search(line)
        comp.append((m.group(2), m.group(3), op.group(1) if op else None,
                     _CALLS.findall(line), bool(m.group(1))))
    return comps


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of an optimized HLO module's text;
    instructions with no ``op_name``, own or called, are left out."""
    comps = parse_hlo(hlo_text)

    def own_or_called(instr):
        _, _, op, calls, _ = instr
        if op:
            return op
        for c in calls:
            # the called computation's root first
            for sub in sorted(comps.get(c, []), key=lambda x: not x[4]):
                found = own_or_called(sub)
                if found:
                    return found
        return None

    out = {}
    for body in comps.values():
        for instr in body:
            op = own_or_called(instr)
            if op:
                out[instr[0]] = op
    return out


def step_hlo():
    """The optimized HLO text of the train step of the cell that the
    command's ``--workload`` names (``chipbench/run.py``), or None: the
    step built again as the runner builds it, lowered for the shapes and
    device of the run's state and batches, and compiled (the compile
    cache serves it)."""
    import jax

    from chipbench import train

    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--workload")
    name = ap.parse_known_args()[0].workload
    if name is None:
        return None
    cell = common.cell(name)
    if cell["workload"]["runner"] != "train":
        return None
    prog = train.Program(cell)
    kd = common.key_data(0)
    shapes = jax.eval_shape(lambda: (prog.init(kd), prog.batches.ring(
        prog.spec, prog.m["vocab"], 0, count=1)[0]))
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    state, batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
        shapes)
    return prog.step.lower(state, batch).compile().as_text()


def instruction(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def whole_steps(red) -> list:
    """The train-step executions wholly inside the window, as ``(start,
    end)``, without the stub of the one the profiler cut short."""
    steps = red.modules(lambda n: STEP in n)
    if not steps:
        return []
    half = 0.5 * common.median([end - start for start, end in steps])
    return [(start, end) for start, end in steps if end - start >= half]


def split_s(red, names: dict):
    """Device seconds per train step under each scope and
    :data:`UNSCOPED`, with the step's busy seconds (``busy``) and the
    unscoped operations that took most time (``top_unscoped``); None when
    no step lies wholly inside the window or no operation falls under a
    scope (a program without the scopes)."""
    steps = whole_steps(red)
    if not steps:
        return None
    per = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    loose = collections.Counter()
    busy = 0.0
    for d in red.devices:
        ops = tracing.leaves(d["ops"])
        for lo, hi in steps:
            busy += tracing.busy_ns(d["ops"], lo, hi)
            for ev in ops:
                c = tracing.clip(ev, lo, hi)
                if c is None:
                    continue
                name = instruction(ev[0])
                scope = scope_of(names.get(name))
                per[scope] += c[1] - c[0]
                if scope == UNSCOPED:
                    loose[tracing.short(ev[0])] += c[1] - c[0]
    if not any(per[s] for s in SCOPES):
        return None
    k = 1e-9 / len(red.devices) / len(steps)
    out = {s: v * k for s, v in per.items()}
    out["busy"] = busy * k
    out["top_unscoped"] = [[n, v * k] for n, v in loose.most_common(10)]
    return out


def per_step_s(rec: dict):
    """:func:`split_s` of the run's trace, read once per run.  The
    instruction map is ``rec["op_names"]`` where the runner put one there,
    else :func:`op_names` of :func:`step_hlo`."""
    if "scopes" not in rec:
        red, split = rec.get("reduced"), None
        if rec["kind"] == "train" and red is not None:
            if "op_names" not in rec:
                t0 = time.perf_counter()
                text = step_hlo()
                rec["op_names"] = op_names(text) if text else None
                common.log(f"op_name map of {len(rec['op_names'] or ())} "
                           f"instructions in {time.perf_counter() - t0:.2f} s")
            if rec["op_names"]:
                split = split_s(red, rec["op_names"])
        if split is not None:
            common.log(f"scopes, device s per train step: {split}")
        rec["scopes"] = split
    return rec["scopes"]


def ms(rec: dict, scope: str):
    """Device milliseconds per train step under ``scope``."""
    split = per_step_s(rec)
    return None if split is None else 1e3 * split[scope]


def unscoped_share(rec: dict):
    """Unscoped leaf device time over all leaf device time, in %."""
    split = per_step_s(rec)
    if split is None:
        return None
    return 100.0 * split[UNSCOPED] / sum(split[s] for s in
                                         SCOPES + (UNSCOPED,))
