"""Model FLOP/s utilization of training, in %: 3 x forward FLOPs per
example (forward and backward) x the traced run's
``train_examples_per_s`` over the chip's bf16 peak."""


def read(rec):
    rate = rec.e2e.get("train_examples_per_s")
    if rate is None:
        return None
    return 100.0 * 3 * rec.flops * rate / rec.peaks["bf16_flops_per_s"]
