"""`counts/kda.py`, `counts/hybrid_cache.py` and `counts/moe_share.py` at the
sizes of `configs/solar-open2-250b-serve.json`, against hand arithmetic and
against what the program allocates."""

import os

import pytest

from perfbench import flops, loader
from perfbench.builders import solar_open2
from perfbench.counts import hybrid_cache, kda, moe_grouped, moe_share


@pytest.fixture(scope="module")
def config():
    return loader.read_json(os.path.join(
        loader.HERE, "configs", "solar-open2-250b-serve.json"))


@pytest.fixture(scope="module")
def sizes(config):
    return solar_open2.model_sizes(config)


def test_cache_bytes_are_the_files(config, sizes):
    on_chip = config["bytes_on_chip"]
    assert kda.linear_layers(sizes) == 3 and hybrid_cache.full_layers(sizes) == 1
    assert hybrid_cache.kv_bytes_per_token(sizes) == 4096 == on_chip["kv_bytes_per_token"]
    # the accepted count multiplies by every layer: four times too much here
    assert flops.kv_bytes_per_token(sizes) == 4 * hybrid_cache.kv_bytes_per_token(sizes)
    assert kda.state_bytes(sizes) == 64 * 128 * 128 * 4
    per_slot = hybrid_cache.state_bytes_per_slot(sizes)
    assert per_slot == 3 * (4194304 + 3 * 24576 * 2) == on_chip["state_bytes_per_slot"]
    engine = config["engine"]
    assert per_slot * engine["max_batch_slots"] == on_chip["slot_state"]
    assert per_slot * on_chip["snapshots"] == on_chip["snapshot_pool"]
    assert (engine["num_pages"] * engine["page_size"]
            * hybrid_cache.kv_bytes_per_token(sizes) == on_chip["kv_pool"])
    # a snapshot costs what some 3.2k tokens of keys and values cost
    assert 3100 < hybrid_cache.snapshot_worth_tokens(sizes) < 3300
    assert hybrid_cache.paged_decode_bytes(1000, sizes) == 4096000


def test_kda_counts(sizes):
    # one chunk of 64 tokens, one head: by hand
    c, dk, dv = 64, 128, 128
    per_head = (2 * c * c * dk + c ** 3 // 3 + c * c * (dk + dv)
                + 6 * c * dk * dv + c * c * dv)
    assert kda.chunked_flops(64, sizes) == 64 * per_head
    assert kda.chunked_flops(65, sizes) == 2 * kda.chunked_flops(64, sizes)
    assert kda.chunked_flops(1024, sizes) == 16 * kda.chunked_flops(64, sizes)
    # a few per cent of the layer's projections (2 x 137.7M a token)
    assert 0.02 < kda.chunked_flops(1024, sizes) / (1024 * 2 * 137.7e6) < 0.08
    assert kda.chunked_bytes(0, sizes) == 2 * kda.state_bytes(sizes)
    assert (kda.chunked_bytes(10, sizes) - kda.chunked_bytes(0, sizes)
            == 10 * 64 * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 4))
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert kda.chunked_least_seconds(1024, sizes, peaks) == pytest.approx(max(
        kda.chunked_flops(1024, sizes) / 197e12,
        kda.chunked_bytes(1024, sizes) / 819e9))
    assert kda.decode_bytes(16, sizes) == 16 * 3 * 2 * 4194304
    dense = dict(sizes, full_attn_every=0)
    assert kda.linear_layers(dense) == 0 and kda.decode_bytes(16, dense) == 0


def test_the_share_of_the_experts(sizes):
    assert moe_share.expert_bytes(sizes) == 3 * 4096 * 1280 * 2
    assert moe_share.expert_bytes(sizes) == moe_grouped.expert_bytes(sizes)
    # 16 rows x 8 a token over 320 experts: one pair in eight is held
    assert moe_share.held_pairs(16, sizes) == 16.0
    # the accepted count takes every pair for held: eight times the rows
    assert moe_grouped.grouped_flops(16, sizes) == 8 * moe_share.share_flops(16.0, sizes)
    touched = moe_share.experts_touched(16.0, sizes)
    assert 12 < touched < 14            # about 13 of the chip's 40
    assert moe_share.experts_touched(1e6, sizes) == pytest.approx(40)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = moe_share.least_seconds(16.0, sizes, peaks, touched=13.0)
    assert least == pytest.approx(
        (13 * 31457280 + 16 * 2 * (2 * 4096 + 2 * 1280)) / 819e9)
    # a chunk of 1024 tokens (1024 pairs) still waits for the 40 experts'
    # weights; 16384 pairs are compute-bound
    chunk = moe_share.held_pairs(1024, sizes)
    assert moe_share.least_seconds(chunk, sizes, peaks) == pytest.approx(
        moe_share.share_bytes(chunk, sizes) / 819e9)
    big = moe_share.held_pairs(16384, sizes)
    assert moe_share.least_seconds(big, sizes, peaks) == pytest.approx(
        moe_share.share_flops(big, sizes) / 197e12)
