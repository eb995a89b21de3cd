"""Device time of the jitted train step, per step and per chip: the summed
durations of its executions in the trace (chip-seconds, summed over the
cell's chips) over the chips and the steps of the window (ms). On N chips
that run the step together it reads one chip's time a step."""


def read(rec):
    t = rec.trace
    if t is None or rec.steps == 0 or "train_step" not in t.module_s:
        return None
    return t.module_s["train_step"] / t.n_chips / rec.steps * 1e3
