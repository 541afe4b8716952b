"""Work functions and peaks, checked against counts made by hand at the
four cells' shapes."""

import math

from bench import spec

PEAKS = spec.load_json(spec.BENCH / "peaks.json")


def work(kernel, cell):
    c = spec.resolve(cell)
    return spec.load_module("work", kernel).work(c.config, c.traffic)


def test_peaks():
    v5e = PEAKS["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


def test_fused_xent_odp():
    # N 512, nnz 120, d 422,713, R*B 800: 61,440 entries
    w = work("fused_xent", "odp.train")
    assert w["flops"] == 4 * 61440 * 800 == 196_608_000
    entries = 61440 * 8                 # int32 column + f32 value
    w_rows = 61440 * 800 * 4            # the W rows the entries name
    small = 800 * 4 + 512 * 25 * 4 + 512 * 4 + 800 * 4
    dw = 422713 * 800 * 4               # 1.35 GB, written once
    assert w["bytes"] == entries + w_rows + small + dw
    assert math.isclose(w["bytes"], 1.5506e9, rel_tol=1e-3)


def test_fused_xent_imagenet21k():
    # N 1,024, d 6,144, R*B 10,240
    w = work("fused_xent", "imagenet21k.train")
    assert w["flops"] == 4 * 1024 * 6144 * 10240 == 257_698_037_760
    x = 1024 * 6144 * 4
    wt = 6144 * 10240 * 4
    small = 10240 * 4 + 1024 * 20 * 4 + 1024 * 4 + 10240 * 4
    assert w["bytes"] == x + 2 * wt + small


def test_topk():
    w = work("topk", "odp.decode")
    assert w["flops"] == 256 * 105033 * 25
    assert w["bytes"] == 256 * 800 * 4 + 25 * 105033 * 4 + 256 * 10 * 8
    w = work("topk", "imagenet21k.decode")
    assert w["flops"] == 256 * 21841 * 20
    assert w["bytes"] == 256 * 10240 * 4 + 20 * 21841 * 4 + 256 * 10 * 8


def test_projection():
    assert work("mach_projection", "odp.decode")["flops"] == \
        2 * 256 * 120 * 800
    assert work("mach_projection", "imagenet21k.decode")["flops"] == \
        2 * 256 * 6144 * 10240


def test_bounds_by_roofline():
    """Which side of the roofline bounds each kernel on a v5e."""
    v5e = PEAKS["TPU v5 lite"]

    def bound(kernel, cell):
        w = work(kernel, cell)
        return ("flops" if w["flops"] / v5e["bf16_flops_per_s"]
                >= w["bytes"] / v5e["hbm_bytes_per_s"] else "bytes")

    assert bound("fused_xent", "odp.train") == "bytes"
    assert bound("fused_xent", "imagenet21k.train") == "flops"
    assert bound("topk", "odp.decode") == "bytes"
    assert bound("topk", "imagenet21k.decode") == "bytes"
