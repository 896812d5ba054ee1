"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    red = rec.get("reduced")
    if rec["kind"] != "train" or red is None:
        return None
    return 100.0 * red.idle_share
