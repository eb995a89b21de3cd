"""Rate of the restore's host->device placement: the ``bytes`` of the
window's ``cnr.restore.place`` spans (``restore_train_state`` through
``block_until_ready``) over their summed seconds (GB/s)."""

from bench_program import rate_gbps


def read(rec):
    return rate_gbps(rec, "cnr.restore.place")
