"""The required-FLOPs functions kept beside the benchmark, the peaks
table and the roofline arithmetic."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import manifest, roofline  # noqa: E402

BENCH = manifest.load_manifest(ROOT)


def _flops(config_name):
    return (manifest.load_module(ROOT, "flops", config_name),
            manifest.load_config(ROOT, BENCH, config_name))


def test_resnet18_forward_is_0_557_gmac_within_one_percent():
    module, config = _flops("resnet18_cifar10")
    macs = module.forward_macs(config)
    assert abs(macs["conv"] + macs["fc"] - 0.557e9) / 0.557e9 < 0.01
    layers = module.conv_layers(config)
    # stem + 8 blocks x 2 convs + 3 projections
    assert len(layers) == 20
    assert layers[0] == ("stem", 32, 3, 3, 64, 1)
    assert layers[-1] == ("s3b1.conv2", 4, 3, 512, 512, 1)
    assert [l for l in layers if l[0].endswith("proj")] == [
        ("s1b0.proj", 16, 1, 64, 128, 2), ("s2b0.proj", 8, 1, 128, 256, 2),
        ("s3b0.proj", 4, 1, 256, 512, 2)]


def test_resnet18_round_counts_real_samples_only():
    module, config = _flops("resnet18_cifar10")
    job = {"n_samples": [48] * 32, "batch": 32, "local_epochs": 1}
    r = module.required(config, job)
    per_sample = 6 * (555_417_600 + 5_120)
    assert r["flops_per_sample"] == per_sample
    # 48 samples sit in 64 slots; the 16 padded slots count nothing
    assert r["flops_per_round"] == per_sample * 48 * 32
    assert r["kernel"] == "conv"
    assert r["kernel_flops_per_round"] == 6 * 555_417_600 * 48 * 32
    two_epochs = module.required(config, dict(job, local_epochs=2))
    assert two_epochs["flops_per_round"] == 2 * r["flops_per_round"]
    # compute is the bound that applies to the convolutions
    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    _, bound = roofline.least_seconds(r["kernel_flops_per_round"],
                                      r["kernel_bytes_per_round"], peaks)
    assert bound == "compute"


def test_bert_count_equals_the_closed_form_and_embeddings_count_zero():
    module, config = _flops("bert_base")
    h, f, layers, seq = 768, 3072, 12, 128
    macs = module.forward_macs(config, seq)
    assert macs["embeddings"] == 0
    assert macs["blocks"] == layers * seq * (4 * h * h + 2 * h * f)
    assert macs["attention"] == layers * 2 * seq * seq * h
    assert macs["pooler"] == h * h and macs["head"] == h * 4
    job = {"n_samples": [96] * 12, "batch": 32, "local_epochs": 1,
           "seq_len": seq}
    r = module.required(config, job)
    closed = 6 * (layers * seq * (4 * h * h + 2 * h * f + 2 * seq * h)
                  + h * h + 4 * h)
    assert r["flops_per_sample"] == closed
    assert r["flops_per_round"] == closed * 96 * 12
    assert r["kernel"] == "matmul"
    # 5.24e8 FLOP a token: 6 x 85 M block parameters + attention, and not
    # 6 x 109 M (which counts the 30,522 x 768 table as a matmul)
    assert 5.2e8 < r["flops_per_token"] < 5.3e8
    n_with_table = 84_934_656 + 30_522 * 768
    assert 6 * n_with_table > 1.2 * r["flops_per_token"]


def test_peaks_are_keyed_by_exact_device_kind():
    peaks = manifest.load_peaks(ROOT, "TPU v5 lite")
    assert peaks["flops_per_s_bf16"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    for unknown in ("TPU v5", "TPU v5 lite pod", "cpu", ""):
        with pytest.raises(KeyError):
            manifest.load_peaks(ROOT, unknown)


def test_least_seconds_names_the_bound():
    peaks = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_seconds(200.0, 10.0, peaks) == (2.0, "compute")
    assert roofline.least_seconds(200.0, 50.0, peaks) == (5.0, "memory")


def test_roofline_share_divides_work_over_devices():
    cell = {"required": {"kernel": "conv", "kernel_flops_per_round": 400.0,
                         "kernel_bytes_per_round": 1.0},
            "peaks": {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}}
    one = {"devices": {"a": {"category_s": {"mxu": 8.0}}}, "n_rounds": 1}
    assert roofline.roofline_share(one, cell, "conv") == 50.0
    four = {"devices": {k: {"category_s": {"mxu": 2.0}} for k in "abcd"},
            "n_rounds": 1}
    assert roofline.roofline_share(four, cell, "conv") == 50.0
    assert roofline.roofline_share(one, cell, "matmul") is None
    assert roofline.roofline_share(None, cell, "conv") is None
    none_ran = {"devices": {"a": {"category_s": {}}}, "n_rounds": 1}
    assert roofline.roofline_share(none_ran, cell, "conv") is None
