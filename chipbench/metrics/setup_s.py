"""Seconds from process start until the window opens: imports, weights,
compilation (or loading it from the cache), warm-up and warm traffic."""


def read(rec):
    return rec["setup_s"]
