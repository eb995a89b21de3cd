"""Mean over the window's restores of a restore's ``cnr.restore.fetch`` spans,
summed over the pool's workers: the store gets of the chain's chunks and
dense blobs (s)."""

from bench_program import mean, per_restore


def read(rec):
    return mean(per_restore(rec, "cnr.restore.fetch"))
