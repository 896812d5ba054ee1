"""Median of the engine's own ``decode_step_s`` samples taken in the window
(host clock around the padded-batch decode step, which blocks on it)."""
from chipbench import common


def read(rec):
    ms = rec.get("decode_ms")
    return common.median(ms) if ms else None
