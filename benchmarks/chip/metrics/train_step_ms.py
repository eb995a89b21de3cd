"""Device time of the jitted train step, per step: the summed durations of
its executions in the trace over the steps of the window (ms)."""


def read(rec):
    t = rec.trace
    if t is None or rec.steps == 0 or "train_step" not in t.module_s:
        return None
    return t.module_s["train_step"] / rec.steps * 1e3
