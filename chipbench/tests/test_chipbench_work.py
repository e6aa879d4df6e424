"""``work.py`` against counts made by hand at the benchmark's shapes."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import work  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def config(name):
    with open(HERE.parent / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name,layer,weight_bytes,kv", [
    # 896*64*(14+2*2) + 14*64*896 + 3*896*4864 (SwiGLU)
    # 2 B * (24 layers * layer + 151936*896 tied head)
    # 24 layers * (k, v) * 2 heads * 64 * 2 B
    ("qwen2-0.5b", 1_032_192 + 802_816 + 13_074_432,
     2 * (24 * 14_909_440 + 136_134_656), 24 * 2 * 2 * 64 * 2),
    # 6144*128*(48+4*2) + 48*128*6144 + 2*6144*24576 (plain GELU)
    # 2 B * (10 layers * layer + 6144*49152 untied head)
    ("starcoder2-15b-l10", 44_040_192 + 37_748_736 + 301_989_888,
     2 * (10 * 383_778_816 + 301_989_888), 10 * 2 * 4 * 128 * 2),
])
def test_weights_and_cache_by_hand(name, layer, weight_bytes, kv):
    cfg = config(name)
    assert work.layer_params(cfg) == layer
    assert work.weight_bytes(cfg) == weight_bytes
    assert work.kv_bytes_per_position(cfg) == kv


def test_qwen2_prefill_and_decode_by_hand():
    cfg = config("qwen2-0.5b")
    layers = 24 * 14_909_440
    head = 896 * 151_936
    attn = 24 * 4 * 14 * 64          # per (query, key) pair, all layers
    pre = work.prefill(cfg, 1, 512)
    assert pre.flops == 2 * layers * 512 + attn * (512 * 513 // 2) + 2 * head
    assert pre.bytes == 987_922_432 + 512 * 12_288
    dec = work.decode(cfg, 1, 512, 63)
    pairs = sum(512 + 1 + i for i in range(63))
    assert dec.flops == 63 * (2 * layers + 2 * head) + attn * pairs
    assert dec.bytes == 63 * 987_922_432 + (pairs + 63) * 12_288
    # rows scale the work, not the weights read
    dec8 = work.decode(cfg, 8, 512, 63)
    assert dec8.flops == 8 * dec.flops
    assert dec8.bytes == 63 * 987_922_432 + 8 * (pairs + 63) * 12_288


def test_starcoder2_prefill_is_compute_bound_and_decode_bandwidth_bound():
    cfg = config("starcoder2-15b-l10")
    pre = work.prefill(cfg, 16, 1024)
    assert pre.flops / PEAKS["flops_per_s"] > pre.bytes / PEAKS["bytes_per_s"]
    assert pre.least_seconds(PEAKS) == pre.flops / PEAKS["flops_per_s"]
    dec = work.decode(cfg, 1, 1024, 31)
    assert dec.least_seconds(PEAKS) == dec.bytes / PEAKS["bytes_per_s"]


def test_request_is_prefill_plus_decode():
    cfg = config("qwen2-0.5b")
    req = work.request(cfg, 512, 64)
    assert req.flops == (work.prefill(cfg, 1, 512).flops
                         + work.decode(cfg, 1, 512, 63).flops)
