"""Rate of the snapshot's device->host copy: the ``bytes`` of the
window's ``cnr.snapshot.copy`` spans over their summed seconds (GB/s)."""

from bench_program import rate_gbps


def read(rec):
    return rate_gbps(rec, "cnr.snapshot.copy")
