"""The FLOP and byte counters, the harness's own (`counters`) and the
`gpt2` family's (`G`), against numbers worked by hand."""
import pytest

import counters as C
import find
import weights as W

G = find.load("families", "gpt2")
MED = G.sizes(W.load_config("gpt2-medium"))
LRG = G.sizes(W.load_config("gpt2-large"))


def test_matmul_and_total_params():
    # medium: 24 x 12 x 1024^2 = 301,989,888; head 1024 x 50257
    assert G.matmul_params(MED) == 301_989_888 + 51_463_168
    # large: 36 x 12 x 1280^2 = 707,788,800; head 1280 x 50257
    assert G.matmul_params(LRG) == 707_788_800 + 64_328_960
    # all leaves: the published 354.8 M / 774.0 M + the untied head + bias
    assert G.total_params(MED) == 354_823_168 + 51_463_168 + 50_257
    assert G.total_params(LRG) == 774_030_080 + 64_328_960 + 50_257


def test_train_flops_per_token():
    # 6 x 353,453,056 = 2,120,718,336; attention 3 x 24 x 4 x 1024 x
    # (1024 x 1025 / 2) / 1024 = 151,142,400
    assert G.train_flops_per_token(MED, 1024) == pytest.approx(
        2_120_718_336 + 151_142_400)
    assert G.train_flops_per_token(LRG, 1024) == pytest.approx(
        6 * 772_117_760 + 3 * 36 * 4 * 1280 * 512.5)


def test_serve_flops_and_decode_bytes():
    # one prompt of 3 tokens (6 pairs) and 2 decoded tokens at contexts 4, 5
    got = G.serve_flops(MED, prefill_pairs=6, prefill_tokens=3,
                        decode_pairs=9, decode_tokens=2)
    assert got == 2 * 353_453_056 * 5 + 24 * 4 * 1024 * 15
    # every weight but the two tables, float32, + live K/V of 1,000 tokens
    w = (G.total_params(MED) - (50_257 + 1024) * 1024) * 4
    assert G.decode_step_bytes(MED, 1000) == w + 2 * 24 * 1024 * 1000 * 4
    assert G.kv_bytes_per_token(MED) == 2 * 24 * 1024 * 4


def test_kernel_counts():
    # flash forward, 8 x 16 heads, S 1024, dh 64, bf16: 128 x 524,800 pairs
    fl, by = C.flash_fwd(8, 16, 1024, 64, 2)
    assert fl == 4 * 64 * 128 * 524_800
    assert by == 4 * 128 * 1024 * 64 * 2 + 128 * 1024 * 4
    assert G.flash_call_shape(MED, 8, 1024) == (8, 16, 1024, 64)
    fl2, by2 = C.flash_bwd(8, 16, 1024, 64, 2)
    assert fl2 == 2 * fl and by2 == 8 * 128 * 1024 * 64 * 2 + 2 * 128 * 1024 * 4
    peak = C.peaks("TPU v5 lite")
    assert C.roofline_seconds(fl, by, peak)[1] == "flops"
    assert C.roofline_seconds(1.0, 1e6, peak)[1] == "bytes"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        C.peaks("TPU v9")
    with pytest.raises(KeyError):
        C.peaks("source")
