"""Mean host time of ``Trainer.checkpoint()`` over the window's saves: the
snapshot copy device->host and the wait for the previous save (s)."""


def read(rec):
    if not rec.saves:
        return None
    return sum(s["stall_s"] for s in rec.saves) / len(rec.saves)
