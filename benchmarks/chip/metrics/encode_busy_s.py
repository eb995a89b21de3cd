"""Mean over the window's saves of a save's ``cnr.save.encode`` spans,
summed over the encode workers: row gather, quantize and the chunk's
layout (s)."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.save.encode"))
