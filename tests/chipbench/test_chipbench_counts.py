"""FLOP and byte counts from shapes, through each configuration's family,
against numbers worked by hand."""
import json

import pytest
from conftest import REPO

from chipbench import counts, spec


def shape(name: str):
    c = json.loads((REPO / "chipbench" / "configs" / f"{name}.json").read_text())
    family = spec.family(REPO, c, name)
    return family.Shape.of(family.program_config(c))


# the last two: a 512-token prefill call from position 0 whose last token's
# logits are used, one lane reaching 512 positions
@pytest.mark.parametrize("name,layer,params,kv,flops_512,bytes_512", [
    # 2*5120*40*128 + 2*5120*8*128 + 3*5120*13824 + (40+16)*128 bias + 2*5120 norms
    ("qwen2.5-14b", 275_268_608, 4_860_363_776, 49_152, 3_416_119_050_240, 8_219_166_720),
    # 2*7168*56*128 + 2*7168*8*128 + 3*7168*20480 + 2*7168 norms
    ("yi-34b", 557_856_768, 5_380_365_312, 32_768, 4_600_886_198_272, 9_884_121_088),
])
def test_parameters_and_kv(name, layer, params, kv, flops_512, bytes_512):
    s = shape(name)
    assert s.layer_params == layer
    assert s.params == params
    assert s.kv_bytes_per_token == kv
    if name == "qwen2.5-14b":
        assert 2 * s.params == 9_720_727_552  # parameter bytes measured on the chip
        assert shape("qwen2.5-14b-paged") == s
    toks = [(p, p == 511) for p in range(512)]
    assert s.call_flops(toks) == flops_512
    assert s.call_bytes(toks, [512]) == bytes_512


def test_token_flops_by_hand():
    s = shape("qwen2.5-14b")
    # 12 * (2 * 275,251,200 matrix params + 4 * 40 * 128 * 1 key)
    assert s.token_flops(1, logits=False) == 6_606_274_560
    assert s.token_flops(1, logits=True) == 6_606_274_560 + 2 * 5120 * 152_064
    assert s.token_flops(101, False) - s.token_flops(1, False) == 12 * 4 * 40 * 128 * 100


def test_call_bytes_and_roofline():
    s = shape("qwen2.5-14b")
    toks = [(99, True), (9, True)]  # two live decode lanes
    weights = 2 * (12 * 275_268_608 + 152_064 * 5120 + 5120)
    assert s.weight_bytes_per_call == weights == 8_163_592_192
    want = weights + 2 * 5120 * 2 + (100 + 10) * 49_152 + 2 * 49_152
    assert s.call_bytes(toks, [100, 10]) == want
    assert s.call_flops(toks) == s.token_flops(100, True) + s.token_flops(10, True)
    t, bound = counts.roofline_s(s.call_flops(toks), want, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(want / 819e9)
    t, bound = counts.roofline_s(1e15, 1e9, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)
