"""Share of the traced span, from the window's start to a few seconds past
the first token, in which no operation ran on the device (per cent)."""


def read(rec):
    t = rec.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
