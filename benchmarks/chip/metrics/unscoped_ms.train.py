"""Device time of the ops that carry no scope, themselves or in what
they call (relayouts and copies the compiler inserts, or an op whose
instruction the step's text does not name), in ms per train step of
the traced window's ``steps`` + 1."""
SCOPES = ("",)


def read(rec):
    return rec.scope_ms(SCOPES, rec.stats["steps"] + 1)
