"""Backend compiles during set-up, over every function
(``compile_stats()``; a persistent-cache hit counts as one)."""


def read(rec):
    if not rec.setup_compiles:
        return None
    return sum(r["compiles"] for r in rec.setup_compiles.values())
