"""Idle share of the chips over the traced window of a train cell: 1
minus each chip's busy time (the union of its operation intervals) over
the window, averaged over the chips, which are the device planes that
hold ops (%)."""


def read(rec):
    if rec.trace is None or rec.traffic["mode"] != "train":
        return None
    return 100.0 * rec.trace.idle_share()
