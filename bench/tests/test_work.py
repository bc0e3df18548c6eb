"""Work counts from shapes (the gqa family's), least times, and the
table of peaks."""
import json
from pathlib import Path

import pytest

from bench import families
from bench import peaks as P
from bench import work as W

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
G = families.module("gqa")


def shape(name):
    family, s = families.load(json.loads((CONFIGS / f"{name}.json")
                                         .read_text()))
    assert family is G
    return s


def test_chatglm3_parameters_and_weight_bytes():
    s = shape("chatglm3-6b")
    # the count of the program's own parameter tree: every weight, the
    # tied table once
    assert s.params == 5_977_116_672
    norms = (2 * 28 + 1) * 4096
    assert s.weight_bytes == (s.params - norms) * 2 + norms * 4


def test_decode_step_bytes_follow_live_contexts():
    s = shape("chatglm3-6b")
    kv_tok = 28 * 2 * 2 * 128 * 2          # layers, K and V, heads, hd, bf16
    assert s.kv_bytes_per_token == kv_tok
    # two decoding slots at positions 99 and 9: contexts of 100 and 10
    # positions, one new K/V row each, one embedding row each
    b = G.step_bytes(s, [(99, 1), (9, 1)])
    assert b == s.weight_bytes + (100 + 1 + 10 + 1) * kv_tok + 2 * 4096 * 2
    # a longer context costs exactly its K/V, whatever the table holds
    assert G.step_bytes(s, [(199, 1), (9, 1)]) - b == 100 * kv_tok


def test_window_caps_the_context():
    s = shape("phi3-medium-14b")
    assert s.window == 2047
    assert G.step_bytes(s, [(3000, 1)]) == G.step_bytes(s, [(2046, 1)])
    assert G.token_flops(s, 3000) == G.token_flops(s, 2046)
    assert G.token_flops(s, 100) < G.token_flops(s, 2046)


def test_model_flops_per_token():
    s = shape("chatglm3-6b")
    per_layer = 4096 * (32 + 4) * 128 + 32 * 128 * 4096 + 3 * 4096 * 13696
    attn = 4 * 10 * 32 * 128                     # position 9: 10 keys
    assert G.token_flops(s, 9) == 28 * (2 * per_layer + attn)
    assert G.head_flops(s) == 2 * 4096 * 65024
    # a 3-token prefill chunk from position 5 and one decode at 9
    assert G.step_flops(s, [(5, 3), (9, 1)]) == pytest.approx(
        sum(G.token_flops(s, p) for p in (5, 6, 7, 9)) + 2 * G.head_flops(s))


def test_least_step_time_is_bound_by_hbm_at_decode():
    s = shape("chatglm3-6b")
    pk = P.peaks_for("TPU v5 lite")
    slots = [(500, 1)] * 32
    work = G.step_flops(s, slots), G.step_bytes(s, slots)
    t, bound = W.step_least_seconds(*work, 1, pk)
    assert bound == "hbm"
    assert t == pytest.approx(G.step_bytes(s, slots) / 819e9)
    t4, _ = W.step_least_seconds(*work, 4, pk)
    assert t4 == pytest.approx(t / 4)


def test_gemv_allreduce_bytes_per_call():
    pk = P.peaks_for("TPU v5 lite")
    # phi3-medium FFN-down at tp=4, 16 rows: the 4480 x 5120 weight slice
    t, bound = W.gemv_allreduce_least_seconds(16, 17920, 5120, 4, pk)
    assert bound == "hbm"
    assert t == pytest.approx((4480 * 5120 + 16 * 4480 + 16 * 5120) * 2
                              / 819e9)
    # many rows over one slow link: the all-reduce's bytes bind
    slow = P.Peaks(197e12, 819e12, 1e9, 16e9, "test")
    t, bound = W.gemv_allreduce_least_seconds(256, 17920, 5120, 4, slow)
    assert bound == "ici"
    assert t == pytest.approx(2 * 3 / 4 * 256 * 5120 * 2 / 1e9)


def test_peaks_are_keyed_by_device_kind():
    pk = P.peaks_for("TPU v5 lite")
    assert (pk.flops_bf16, pk.hbm_bytes_s, pk.ici_bytes_s) == (
        197e12, 819e9, 200e9)
    with pytest.raises(KeyError, match="no published peaks"):
        P.peaks_for("cpu")
