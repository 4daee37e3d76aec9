"""Device time of the optimizer (scope ``optimizer``: the clip's norm and
the update rule; ``optimizer.bwd`` where the gradient reaches it), in
ms per train step of the traced window's ``steps`` + 1."""
SCOPES = ("optimizer", "optimizer.bwd")


def read(rec):
    return rec.scope_ms(SCOPES, rec.stats["steps"] + 1)
