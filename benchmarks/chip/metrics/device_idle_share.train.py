"""Idle share of the device over the traced window of a training cell:
1 minus the union of the device's operation intervals over the window
(%)."""


def read(rec):
    if rec.trace is None or rec.traffic["mode"] != "train":
        return None
    return 100.0 * rec.trace.idle_share()
