"""Mean host time of ``restore_train_state`` through
``block_until_ready`` over the window's restores: placing the restored
host arrays on the device (s)."""


def read(rec):
    if not rec.restores:
        return None
    return sum(r["place_s"] for r in rec.restores) / len(rec.restores)
