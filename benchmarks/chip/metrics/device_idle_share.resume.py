"""Idle share of the device over the traced window of a resume cell: 1
minus the union of the device's operation intervals over the window
(%)."""


def read(rec):
    if rec.trace is None or rec.traffic["mode"] != "resume":
        return None
    return 100.0 * rec.trace.idle_share()
