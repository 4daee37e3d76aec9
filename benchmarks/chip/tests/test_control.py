"""The control (the reference one precision step below what the
configuration states) fails the limits, at a small size on the CPU;
and so does the half-batch fault of the training cell."""
import numpy as np
import pytest

import conftest as C
import harness as H
import reference as R


def ctx_for(workload, seed):
    cell = {"deepfm.ctr_online": ("deepfm", "ctr_online_deepfm"),
            "autoint.ctr_bulk": ("autoint", "ctr_bulk_65536"),
            "deepfm.train": ("deepfm", "train_65536")}[workload]
    return H.Ctx(workload, seed, 0.5, False, C.small_config(cell[0]),
                 C.small_traffic(cell[1]), H.load_module("configs", cell[0]))


@pytest.mark.parametrize("workload", ["deepfm.ctr_online",
                                      "autoint.ctr_bulk"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails(workload, seed):
    import gen
    ctx = ctx_for(workload, seed)
    ids = gen.request_ids(gen.rng(seed, 1), 4, 256,
                          ctx.model["field_vocab_sizes"], 1.1)
    exp = H.Expected(ctx, ids)
    ctl, _ = H.reference_scores(ctx, exp.params, exp.flat,
                                R.Prec(control=True))
    exp.free()
    assert np.all(np.isfinite(ctl))
    g = exp.gaps(ctl.reshape(ids.shape[:2]), np.arange(ids.shape[0]))
    assert g.max() > ctx.config["limits"]["score_err"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_and_half_batch_fail(seed):
    drv = H.load_module("drivers", "train_loop")
    import gen
    ctx = ctx_for("deepfm.train", seed)
    vocabs = ctx.model["field_vocab_sizes"]
    ids = gen.request_ids(gen.rng(seed, 1), 3, 256, vocabs, 1.1)
    t = gen.Teacher(vocabs, gen.rng(seed, 3))
    batches = [{"sparse_ids": x, "label": t.labels(x, gen.rng(seed, 4))}
               for x in ids]
    ref = drv.reference_steps(ctx, batches, R.Prec())
    lims = ctx.config["limits"]

    class Got:
        pass

    for prec, bs in ((R.Prec(control=True), batches),
                     (R.Prec(), [{k: v[:128] for k, v in b.items()}
                                 for b in batches])):
        r = drv.reference_steps(ctx, bs, prec)
        got = Got()
        got.losses, got.grad_norms, got.change_norms = (
            r["losses"], r["grad_norms"], r["change_norms"])
        gaps = drv.compare(got, ref)
        assert any(gaps[k] > lims[k] for k in gaps), gaps
