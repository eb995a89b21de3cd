"""Mean over the window's saves of a save's ``cnr.save.row_spans`` spans,
summed over the encode workers: the compression of each incremental
chunk's row ids into the spans its record carries (s). Part of
``encode_busy_s``. A program that opens no such span reads nothing."""

from bench_program import mean, per_save, spans

NAME = "cnr.save.row_spans"


def read(rec):
    if not any(sp.name == NAME for sp in spans(rec)):
        return None
    return mean(per_save(rec, NAME))
