"""Device time of the field embeddings' forward pass (scope ``fields``:
the row gathers, the MGQE assign and codebook decode, the aux loss),
in ms per train step: device ns inside the traced window over the
window's ``steps`` + 1 steps (the first is dispatched before the
driver's loop)."""
SCOPES = ("fields",)


def read(rec):
    return rec.scope_ms(SCOPES, rec.stats["steps"] + 1)
