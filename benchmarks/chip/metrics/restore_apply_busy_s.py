"""Mean over the window's restores of a restore's ``cnr.restore.apply`` spans,
summed over the pool's workers: the ordered scatter of each decoded chunk
into the result arrays (s)."""

from bench_program import mean, per_restore


def read(rec):
    return mean(per_restore(rec, "cnr.restore.apply"))
