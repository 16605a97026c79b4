"""The yardstick's operation and byte counts against hand counts, for
both configurations."""
import json
import pathlib

import pytest

from chipbench import counts
from chipbench import weights as W

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return W.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen2_parameter_count_is_the_published_one():
    m = dims("qwen2-0.5b")
    # per layer: q 896x896, k and v 896x128, o 896x896, three 896x4864
    assert counts.layer_matmul_params(m) == (
        802_816 + 2 * 114_688 + 802_816 + 3 * 4_358_144)
    per_layer = counts.layer_matmul_params(m) + 1_152 + 2 * 896
    total = 24 * per_layer + 151_936 * 896 + 896
    assert total == 494_032_768          # Qwen2-0.5B, tied head


def test_deepseek_slice_weight_bytes():
    m = dims("deepseek-67b-l4")
    assert counts.layer_matmul_params(m) == (
        67_108_864 + 2 * 8_388_608 + 67_108_864 + 3 * 180_355_072)
    # four layers (bf16 matrices, f32 gains), embedding + head, final gain
    want = 4 * (692_060_160 * 2 + 2 * 8192 * 4) + 2 * 838_860_800 * 2 \
        + 8192 * 4
    assert W.weight_bytes(m) == want == 8_892_219_392


@pytest.mark.parametrize("name,batch,prompt,want", [
    # 2 tokens x 24 layers x 2 x 14,909,440 + attention 24 x 4 x 14 x 64
    # x (1 + 2) keys + head 2 x 896 x 151,936
    ("qwen2-0.5b", 1, 2,
     2 * 24 * 2 * 14_909_440 + 24 * 4 * 14 * 64 * 3 + 2 * 896 * 151_936),
    # 3 tokens x 4 layers x 2 x 692,060,160 + 4 x 4 x 64 x 128 x (1+2+3)
    # + head 2 x 8192 x 102,400, for each of 2 rows
    ("deepseek-67b-l4", 2, 3,
     2 * (3 * 4 * 2 * 692_060_160 + 4 * 4 * 64 * 128 * 6
          + 2 * 8192 * 102_400)),
])
def test_prefill_flops(name, batch, prompt, want):
    assert counts.prefill_flops(dims(name), batch, prompt) == want


@pytest.mark.parametrize("name,batch,pos,flops,nbytes", [
    # weights: 24 x (bf16 matrices + f32 gains + bf16 biases) + tied head
    # + final gain; KV 12,288 B a token; logits f32
    ("qwen2-0.5b", 128, 256,
     128 * (24 * 2 * 14_909_440 + 24 * 4 * 14 * 64 * 257
            + 2 * 896 * 151_936),
     24 * (2 * 14_909_440 + 4 * 1_792 + 2 * 1_152) + 2 * 151_936 * 896
     + 4 * 896 + 128 * 256 * 12_288 + 128 * 12_288 + 128 * 151_936 * 4),
    # untied: the head once, plus 32 embedding rows; KV 16,384 B a token
    ("deepseek-67b-l4", 32, 600,
     32 * (4 * 2 * 692_060_160 + 4 * 4 * 64 * 128 * 601
           + 2 * 8192 * 102_400),
     4 * (2 * 692_060_160 + 4 * 16_384) + 2 * 102_400 * 8192 + 4 * 8192
     + 2 * 32 * 8192 + 32 * 600 * 16_384 + 32 * 16_384
     + 32 * 102_400 * 4),
])
def test_decode_step_counts(name, batch, pos, flops, nbytes):
    m = dims(name)
    assert counts.decode_flops(m, batch, pos) == flops
    assert counts.decode_bytes(m, batch, pos) == nbytes
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.decode_roofline_s(m, peaks, batch, pos) == max(
        flops / 197e12, nbytes / 819e9)
