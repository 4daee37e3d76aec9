"""Device time of the first-order weights, forward and gradient (scopes
``first_order`` and ``first_order.bwd``), in ms per train step of the
traced window's ``steps`` + 1."""
SCOPES = ("first_order", "first_order.bwd")


def read(rec):
    return rec.scope_ms(SCOPES, rec.stats["steps"] + 1)
