"""Tokens of every step dispatched in the window over the window's wall
time; the window closes when the last of them is done."""


def read(rec):
    if rec["kind"] != "train":
        return None
    return rec["tokens"] / (rec["w1"] - rec["w0"])
