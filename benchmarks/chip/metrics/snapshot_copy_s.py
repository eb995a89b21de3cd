"""Mean over the window's saves of the ``cnr.snapshot.copy`` span: the
device->host copy of the state and the touched masks, once the dispatched
steps have drained (s)."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.snapshot.copy"))
