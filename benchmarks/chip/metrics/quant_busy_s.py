"""Mean over the window's saves of a save's ``cnr.save.quant`` spans,
summed over the encode workers: the quantize+pack and hash dispatches and
the packed words' copy back to the host (s). Part of ``encode_busy_s``."""

from bench_program import mean, per_save


def read(rec):
    return mean(per_save(rec, "cnr.save.quant"))
