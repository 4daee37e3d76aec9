"""Open-loop requests, served one at a time in arrival order.

Each request is ``batch`` candidate examples x all fields.  Requests
are due at the times of :func:`gen.arrivals`; one is in flight at a
time: upload the ids, call the served step, fetch the scores.  A
request's latency runs from its due time to its scores on the host,
so a slow request delays the ones behind it.  The ids come from a pool
of distinct requests made in set-up, used in turn.
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
        vocabs = ctx.model["field_vocab_sizes"]
        self.pool = gen.request_ids(gen.rng(ctx.seed, 1), t["pool"],
                                    self.batch, vocabs, t["zipf_s"])
        self.due = gen.arrivals(t["rate_rps"], ctx.seconds,
                                gen.rng(ctx.seed, 2))
        for ids in self.pool[:t["warm_requests"]]:
            np.asarray(self.served(jax.device_put(ids)))

    def window(self):
        import jax
        n, p = len(self.due), len(self.pool)
        answers = np.empty((n, self.batch), np.float32)
        send = np.empty(n)
        done = np.empty(n)
        failed = 0
        t0 = time.perf_counter()
        due = t0 + self.due
        for i in range(n):
            with H.span("wait"):
                H.sleep_until(due[i])
            send[i] = time.perf_counter()
            try:
                with H.span("upload"):
                    x = jax.device_put(self.pool[i % p])
                with H.span("call"):
                    s = self.served(x)
                with H.span("fetch"):
                    answers[i] = np.asarray(s)
            except Exception as e:  # a failed request counts as missing
                H.log(f"request {i} failed: {e!r}")
                answers[i] = np.nan
                failed += 1
            done[i] = time.perf_counter()
        lat = np.where(np.isnan(answers[:, 0]), np.inf, done - due)
        on_time = np.concatenate([[True], done[:-1] <= due[1:]])
        self.answers = answers
        return {
            "attempted": n, "failed": failed,
            "metrics": {"request_p95_ms": H.percentile(lat, 95) * 1e3},
            "stats": {"gen_lag_s": (send - due)[on_time],
                      "service_s": done - send, "latency_s": lat,
                      "requests": n, "window_s": done[-1] - t0},
        }

    def free(self):
        self.served.free()

    def check(self):
        which = np.arange(len(self.answers)) % len(self.pool)
        return H.check_scores(self.ctx, self.pool, self.answers, which)


def setup(ctx: H.Ctx) -> Session:
    return Session(ctx)
