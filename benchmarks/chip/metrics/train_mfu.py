"""Model FLOP utilization of the train step on the device: the
yardstick's model FLOPs per step (three times the forward pass) times the
window's steps, over the summed device time of the step's executions in
the trace times the chip's bf16 peak (%). On N chips the step's device
time is in chip-seconds (summed over the chips), so one chip's peak is
right. The save's stalls and the device's idle time are left out: they
are other layers'."""

from bench_yardstick import train_step_flops


def read(rec):
    t = rec.trace
    if t is None or rec.steps == 0 or not t.module_s.get("train_step"):
        return None
    flops = train_step_flops(rec.cfg) * rec.steps
    return 100.0 * flops / (t.module_s["train_step"] * rec.peaks["flops_bf16"])
