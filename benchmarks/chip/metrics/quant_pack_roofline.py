"""Roofline share of the fused quantize+pack kernel: the bytes it must
move for the window's chunks (f32 rows in, packed words and per-row scale
and zero out, over each chunk's real rows, not its power-of-two bucket),
over the summed device time of its executions and one chip's HBM peak
(%). On N chips the time is in chip-seconds, summed over the chips that
run the kernel, so one chip's peak is right. Bytes bound it against the
chip's published peaks: the 4-bit adaptive
search does about 40 vector operations a byte read (9 steps, two
candidate ranges of about 8 operations an element each), under the
197e12 / 819e9 = 240 operations a byte at which compute would bind."""

from bench_yardstick import quant_pack_bytes


def read(rec):
    t = rec.trace
    if t is None or not t.module_s.get("quant_pack_pallas"):
        return None
    need = sum(quant_pack_bytes(n_rows, dim, bits)
               for s in rec.saves for n_rows, dim, bits, _ in s["chunks"])
    least = need / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t.module_s["quant_pack_pallas"]
