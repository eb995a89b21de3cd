"""Mean over the window's saves of a save's ``cnr.save.write`` spans,
summed over the write workers: the store puts of the chunks and dense
blobs (s)."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.save.write"))
