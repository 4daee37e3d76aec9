"""Device time of the field embeddings' gradient (scope ``fields.bwd``:
the tables' gradients, the codebooks' gradients), in ms per train step
of the traced window's ``steps`` + 1."""
SCOPES = ("fields.bwd",)


def read(rec):
    return rec.scope_ms(SCOPES, rec.stats["steps"] + 1)
