"""Offline scoring: batches scored one after the other.

Each step uploads one batch of ``batch`` examples from a ring of host
batches made in set-up, calls the served step, and fetches the scores;
the next starts when the last has reached the host.  The rate is every
example scored over the time from the window's start to the last
scores on the host.
"""
from __future__ import annotations

import time

import numpy as np

import gen
import harness as H


class Session:
    def __init__(self, ctx: H.Ctx):
        import jax
        t = ctx.traffic
        self.ctx = ctx
        self.batch = t["batch"]
        self.served = H.Served(ctx, self.batch)
        self.ring = gen.request_ids(gen.rng(ctx.seed, 1), t["ring"],
                                    self.batch,
                                    ctx.model["field_vocab_sizes"],
                                    t["zipf_s"])
        for ids in self.ring[:t["warm_batches"]]:
            np.asarray(self.served(jax.device_put(ids)))

    def window(self):
        import jax
        r = len(self.ring)
        answers = []
        t0 = time.perf_counter()
        end = t0 + self.ctx.seconds
        done = t0
        while done < end:
            i = len(answers)
            with H.span("upload"):
                x = jax.device_put(self.ring[i % r])
            with H.span("call"):
                s = self.served(x)
            with H.span("fetch"):
                answers.append(np.asarray(s))
            done = time.perf_counter()
        n = len(answers)
        self.answers = np.stack(answers)
        return {
            "attempted": n, "failed": 0,
            "metrics": {"score_examples_per_s":
                        n * self.batch / (done - t0)},
            "stats": {"batches": n, "window_s": done - t0},
        }

    def free(self):
        self.served.free()

    def check(self):
        which = np.arange(len(self.answers)) % len(self.ring)
        return H.check_scores(self.ctx, self.ring, self.answers, which)


def setup(ctx: H.Ctx) -> Session:
    return Session(ctx)
