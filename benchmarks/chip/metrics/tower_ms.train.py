"""Device time of the tower and the loss, forward and gradient (scopes
``tower`` and ``tower.bwd``), in ms per train step of the traced
window's ``steps`` + 1."""
SCOPES = ("tower", "tower.bwd")


def read(rec):
    return rec.scope_ms(SCOPES, rec.stats["steps"] + 1)
