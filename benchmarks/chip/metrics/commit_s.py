"""Mean over the window's saves of the ``cnr.save.commit`` span: the
manifest's build and put, and the post-commit retention and GC (s)."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.save.commit"))
