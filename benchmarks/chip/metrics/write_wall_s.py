"""Mean of the manifests' ``wall_time_s`` over the window's saves: the
writer's own span from the start of its write to the commit (s)."""


def read(rec):
    if not rec.saves:
        return None
    return sum(s["wall_time_s"] for s in rec.saves) / len(rec.saves)
