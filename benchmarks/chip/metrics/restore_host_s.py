"""Mean host time inside ``CheckNRunManager.restore`` over the window's
restores: chain planning, fetch, decode and apply (s)."""


def read(rec):
    if not rec.restores:
        return None
    return sum(r["host_s"] for r in rec.restores) / len(rec.restores)
