"""Share of the traced window in which no op ran on the chip, averaged
over the chips: 1 - busy / window."""


def read(rec):
    tr = rec.get("trace")
    if rec.get("kind") != "serve" or not tr:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
