"""Mean over the window's restores of a restore's ``cnr.restore.decode``
spans, summed over the pool's workers: checksum, unpack and dequantize of
each chunk (s)."""

from bench_program import mean, per_restore


def read(rec):
    return mean(per_restore(rec, "cnr.restore.decode"))
