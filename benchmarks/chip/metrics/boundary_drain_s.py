"""Mean over the window's saves of the ``cnr.snapshot.drain`` span: the
trainer's wait, at the checkpoint boundary, for the steps it dispatched
before the snapshot (s)."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.snapshot.drain"))
