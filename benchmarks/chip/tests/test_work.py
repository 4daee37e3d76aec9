import json
import os

import pytest

import harness as H
import work

CONFIGS = os.path.join(os.path.dirname(work.__file__), "configs")


def model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["model"]


def flops(name):
    """The configuration's own count, from its reference."""
    return H.load_module("configs", name).forward_flops_per_example(
        model(name))


def test_deepfm_flops_by_hand():
    # FM: 3*39*10 + 3*10 = 1,200
    # MLP: 2*(390*400 + 400*400 + 400*400 + 400*1) = 952,800
    assert flops("deepfm") == 954_000


def test_autoint_flops_by_hand():
    # layer 1: 4*2*39*16*64 = 319,488 ; attention 2*2*2*39*39*32 = 389,376
    # layers 2, 3: 4*2*39*64*64 = 1,277,952 + 389,376 each
    # output: 2*39*64 = 4,992
    want = 319_488 + 389_376 + 2 * (1_277_952 + 389_376) + 4_992
    assert want == 4_048_512
    assert flops("autoint") == want


def test_mlp_flops_by_hand():
    assert work.mlp_flops((390, 400, 1)) == 2 * (390 * 400 + 400)
    assert work.mlp_flops((7,)) == 0


def test_decode_bytes_by_hand():
    # deepfm request: codes 512*5 + centroids 5*256*2*4 + rows 512*10*4
    assert work.mgqe_decode_bytes(512, 5, 256, 2) == 2_560 + 10_240 + 20_480
    # autoint bulk batch
    assert work.mgqe_decode_bytes(65_536, 8, 256, 2) == (
        524_288 + 16_384 + 4_194_304)


def test_peaks_known_and_unknown_device():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
