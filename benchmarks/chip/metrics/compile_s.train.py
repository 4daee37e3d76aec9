"""Seconds of backend compiles during set-up, over every function
(``compile_stats()``; a persistent-cache hit counts its load)."""


def read(rec):
    if not rec.setup_compiles:
        return None
    return sum(r["compile_s"] for r in rec.setup_compiles.values())
