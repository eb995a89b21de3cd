"""Roofline share of the on-device chunk hash: the payload words it must
read for the window's chunks, over the summed device time of its
executions and one chip's HBM peak (%). On N chips the time is in
chip-seconds, summed over the chips that run the kernel, so one chip's
peak is right. Bytes bound it."""

from bench_yardstick import chunk_hash_bytes


def read(rec):
    t = rec.trace
    if t is None or not t.module_s.get("chunk_hash_pallas"):
        return None
    need = sum(chunk_hash_bytes(codes_bytes)
               for s in rec.saves for _, _, _, codes_bytes in s["chunks"])
    least = need / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t.module_s["chunk_hash_pallas"]
