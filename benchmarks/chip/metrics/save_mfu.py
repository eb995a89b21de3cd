"""The whole save's share of the cell's peak: the least time the cell's
chips need for a save's device work (every selected row's f32 bytes read
once and the payload written once, at the HBM peak of one chip times the
cell's chips), summed over the window's saves, over their summed walls
from ``Trainer.checkpoint()`` to the manifest commit (%). Bytes bound it."""

from bench_yardstick import save_device_bytes


def read(rec):
    if not rec.saves:
        return None
    least = sum(save_device_bytes(s["rows_by_dim"], s["nbytes"])
                for s in rec.saves) / (rec.peaks["hbm_bytes_per_s"]
                                       * rec.cell["chips"])
    return 100.0 * least / sum(s["durable_s"] for s in rec.saves)
