"""Model FLOP/s utilization of offline scoring, in %: forward FLOPs per
example (the configuration's ``forward_flops_per_example``) x the
traced run's ``score_examples_per_s`` over the chip's bf16 peak."""


def read(rec):
    rate = rec.e2e.get("score_examples_per_s")
    if rate is None:
        return None
    return 100.0 * rec.flops * rate / rec.peaks["bf16_flops_per_s"]
