"""Mean over the window's saves of the ``cnr.save.wait`` span: the
non-overlap wait in ``CheckNRunManager.save`` for the previous save's
writer (s)."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.save.wait"))
