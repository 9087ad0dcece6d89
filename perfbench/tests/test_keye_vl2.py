"""The files `keye-vl2-30b-a3b-serve` and its cell `keye30b-longdoc-batch` bring
(PR 28): the loader finds them by name; the configuration's file holds the
catalog row's numbers; the generator's plan is what the mix's text says; the
plain reference and the program are the same model at rehearsal sizes; the
counts and the new readers compute what they say; the cell rehearses on the
CPU."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import loader
from perfbench.builders import keye_vl2 as builder
from perfbench.counts import moe_grouped, sparse_attention
from perfbench.readers import (group_ratio, moe_grouped_roofline, ops_match,
                               ops_time_share, sparse_decode_roofline)
from perfbench.references import keye_vl2 as reference

CELL, CONFIG = "keye30b-longdoc-batch", "keye-vl2-30b-a3b-serve"
NEW_METRICS = (
    "setup.actor_ready_s.longdoc", "setup.compile_s.longdoc",
    "engine.batch_occupancy_mean.longdoc", "engine.decode_tokens_per_sync.longdoc",
    "engine.prefix_hit_share.longdoc", "wall.decode_sync_share.longdoc",
    "wall.yield_share.longdoc", "wall.demote_share.longdoc",
    "step.decode_ms.longdoc", "attn.selected_key_share.longdoc",
    "attn.select_time_share.longdoc", "moe.computed_rows_per_routed_row.longdoc",
    "kernel.sparse_decode_roofline.longdoc", "kernel.moe_grouped_roofline.longdoc")
# the catalog row's `config` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def bench():
    return loader.benchmark()


# ---------------------------------------------------------------------------
# the loader and the files
# ---------------------------------------------------------------------------

def test_loader_finds_the_cell_and_all_it_is_made_of(bench):
    cell = loader.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc", 1)
    config = loader.config_of(bench, CONFIG)
    assert loader.module("builders", config["builder"]) is builder
    assert loader.reference_of(config) is reference
    sizes = builder.model_sizes(config)
    tol = loader.check_of(config, sizes)
    assert set(tol) == {"median_tol", "far", "far_share"}
    assert len(config["check"]["why"]) > 100
    traffic = loader.traffic_of(cell["traffic"])
    assert hasattr(loader.module("generators", traffic["generator"]), "plan")
    ends = {m["name"] for m in loader.metrics_of(bench, "end_to_end", CELL)}
    assert ends == {"out_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in loader.metrics_of(bench, "per_layer", CELL)}
    assert layer == set(NEW_METRICS)
    for name in NEW_METRICS:
        spec = loader.layer_metric(name)
        assert spec["workloads"] == [CELL]
        assert hasattr(loader.module("readers", spec["reader"]), "read")


def test_configuration_file_is_the_catalog_row_cut_in_depth_only(bench):
    config = loader.config_of(bench, CONFIG)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced"])
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert config["reduced"][key]["source"] == value
            assert config[key] == config["reduced"][key]["here"] == 4
        else:
            assert config[key] == value, key
    for key in ("source", "assumed", "deployment", "bytes_on_chip", "check"):
        assert config[key]
    sizes = builder.model_sizes(config)
    assert (sizes["n_experts"], sizes["top_k"], sizes["expert_dim"],
            sizes["index_topk"], sizes["head_dim"]) == (128, 8, 768, 2048, 128)
    # the file's bytes are the arithmetic of its sizes
    e = config["engine"]
    per_token = sizes["n_layers"] * 2 * (
        2 * sizes["n_kv_heads"] * sizes["head_dim"] + sizes["index_dim"])
    assert config["bytes_on_chip"]["kv_bytes_per_token"] == per_token == 8704
    assert config["bytes_on_chip"]["kv_pool"] == (
        e["num_pages"] * e["page_size"] * per_token)
    d = sizes["d_model"]
    layer = (d * sizes["head_dim"] * 2 * (sizes["n_heads"] + sizes["n_kv_heads"])
             + 2 * sizes["head_dim"]
             + d * (sizes["index_heads"] * sizes["index_dim"]
                    + sizes["index_dim"] + sizes["index_heads"])
             + 2 * sizes["index_dim"]
             + sizes["n_experts"] * 3 * d * sizes["expert_dim"]
             + d * sizes["n_experts"] + 2 * d)
    assert config["bytes_on_chip"]["parameters"] == (
        sizes["n_layers"] * layer + 2 * sizes["vocab"] * d + d)
    assert e["max_seq_len"] == 28672 + 256 + 768


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

def _plan(seed, seconds=45.0, rehearse=False, vocab=151936):
    traffic = loader.traffic_of("longdoc")
    if rehearse:
        traffic = {**traffic, **traffic["rehearsal"]}
    plan = loader.module("generators", traffic["generator"]).plan(
        traffic, seed, seconds, {"vocab": vocab})
    return traffic, plan


@pytest.fixture(scope="module")
def plans():
    return [_plan(seed) for seed in (0, 2**31 + 12345)]


def test_plan_has_the_same_set_of_lengths_on_every_seed(plans):
    (traffic, a), (_, b) = plans
    assert [r["prompt"] for r in a["requests"]] != [
        r["prompt"] for r in b["requests"]]
    for plan in (a, b):
        assert plan["mode"] == "closed" and plan["in_flight"] == 48
        assert plan["ramp"] == 24 and len(plan["setup"]) == 24
        assert len(plan["requests"]) == 24 + 16 * 45 + 48   # never drains
    docs = lambda p: sorted(len(r["prompt"]) for r in p["setup"])  # noqa: E731
    assert [x - y for x, y in zip(docs(a), docs(b))] == pytest.approx(
        [0] * 24, abs=256)         # the same documents, other questions
    for key in ("max_tokens",):
        assert sorted(r[key] for r in a["requests"]) == sorted(
            r[key] for r in b["requests"])
    new = lambda p: sorted(len(r["prompt"]) for r in p["requests"]  # noqa: E731
                           if r["kind"] == "miss")
    assert len(new(a)) == len(new(b)) == 24
    assert [x - y for x, y in zip(new(a), new(b))] == pytest.approx(
        [0] * 24, abs=256)
    _, again = _plan(0)
    assert [r["prompt"] for r in again["requests"]] == [
        r["prompt"] for r in a["requests"]]


def test_plan_opens_one_document_in_32_and_asks_settled_ones(plans):
    traffic, plan = plans[0]
    reqs = plan["requests"]
    assert [r["kind"] for r in reqs[:24]] == ["ramp"] * 24
    assert [r["max_tokens"] for r in reqs[:24]] == [
        round(64 + i * 448 / 23) for i in range(24)]
    body = reqs[24:]
    for start in range(0, len(body) - 31, 32):
        assert sum(r["kind"] == "miss" for r in body[start:start + 32]) == 1
    # at equal distances, and any four consecutive ones hold the same work
    # (a document is some 6% of a window: how many fall into it is its rate)
    misses = [(i, len(r["prompt"])) for i, r in enumerate(body)
              if r["kind"] == "miss"]
    assert {b[0] - a[0] for a, b in zip(misses, misses[1:])} == {32}
    runs = [sum(n for _, n in misses[k:k + 4]) for k in range(0, 24, 4)]
    assert max(runs) - min(runs) <= 4 * 192
    opened = {}
    for i, r in enumerate(reqs):
        doc_len = len(r["prompt"])
        if r["kind"] == "miss":
            opened[r["doc"]] = i
            assert 16384 + 64 <= doc_len <= 28672 + 256
        else:
            assert i - opened.get(r["doc"], -10**9) >= traffic["settle_requests"]
            newest = max(list(opened) + [23])
            assert newest - 23 <= r["doc"] <= newest      # one of the 24 latest
        assert 64 <= r["max_tokens"] <= 768
    # a further question's prompt is its document and then fresh tokens
    docs = {d: r["prompt"] for d, r in
            ((-1 - s["rid"], s) for s in plan["setup"])}
    hit = next(r for r in body if r["kind"] == "hit" and r["doc"] < 24)
    shared = 0
    while hit["prompt"][shared] == docs[hit["doc"]][shared]:
        shared += 1
    assert shared >= 16384


def test_documents_fit_the_pool_and_every_request_its_row(bench, plans):
    config = loader.config_of(bench, CONFIG)
    e = config["engine"]
    _, plan = plans[0]
    longest = max(len(r["prompt"]) + r["max_tokens"]
                  for r in plan["setup"] + plan["requests"])
    assert longest <= e["max_seq_len"]
    # the 24 most recent documents, the one being opened, and a question and
    # an answer in every slot, in pages
    pages = lambda n: -(-n // e["page_size"])   # noqa: E731
    docs = sorted((len(r["prompt"]) for r in plan["setup"]), reverse=True)
    resident = (sum(pages(n) for n in docs) + pages(28672 + 256)
                + e["max_batch_slots"] * pages(256 + 768))
    assert resident < 0.8 * e["num_pages"]
    assert plan["warm"] == {"prompt_min": 16384 + 64,
                            "prompt_max": 28672 + 256, "sharing": True}
    assert min(plan["check_prompt_lens"]) >= 2048 + 512


# ---------------------------------------------------------------------------
# the reference against the program, at rehearsal sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from ray_tpu.models.llama import Llama, LlamaConfig
    sizes = builder.model_sizes({}, rehearse=True)
    cfg = LlamaConfig.keye_tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                max_seq_len=512)
    assert (cfg.d_model, cfg.n_experts, cfg.index_topk, cfg.expert_dim) == (
        sizes["d_model"], sizes["n_experts"], sizes["index_topk"],
        sizes["expert_dim"])
    model = Llama(cfg)
    params = model.init(builder.seed_key(2**31 + 7), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(x.size), x.shape, x.dtype) if x.ndim == 1 else x,
        params)
    return model, params, sizes


@pytest.mark.parametrize("n_tokens", [14, 200])   # below and far above topk 16
def test_logprobs_match_the_program(tiny, n_tokens):
    model, params, sizes = tiny
    tokens = np.random.default_rng(0).integers(0, sizes["vocab"], n_tokens)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(params, jnp.asarray(tokens[None, :-1]))
    logp = jax.nn.log_softmax(logits[0], axis=-1)
    want = np.asarray(logp[np.arange(n_tokens - 1), tokens[1:]])[-9:]
    got = np.asarray(reference.logprobs_of(params, tokens, sizes, 9))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_has_three_rotary_components_and_no_program_import(tiny):
    model, params, sizes = tiny
    t = 60
    tokens = np.random.default_rng(1).integers(0, sizes["vocab"], t)
    pos3 = jnp.stack([jnp.arange(t), jnp.arange(t) // 4, jnp.arange(t) % 7])
    want, _ = model.apply(params, jnp.asarray(tokens[None]),
                          positions=pos3[:, None], return_hidden=True)
    got = reference.hidden_states(params, tokens, sizes, positions=pos3)
    np.testing.assert_allclose(got, want[0], atol=2e-5)
    text = reference.hidden_states(params, tokens, sizes)
    assert float(jnp.abs(got - text).max()) > 1e-2
    src = open(reference.__file__).read()
    assert "ray_tpu" not in src.replace("imports\nnothing of the program", "")
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_a_reference_without_the_selection_is_seen(tiny, monkeypatch):
    """With every causal key kept, the reference leaves the program past
    topk tokens by far more than the two differ otherwise."""
    _, params, sizes = tiny
    tokens = np.random.default_rng(2).integers(0, sizes["vocab"], 120)
    real = np.asarray(reference.logprobs_of(params, tokens, sizes, 9))
    monkeypatch.setattr(reference, "_selected",
                        lambda scores, topk: scores > -jnp.inf)
    reference._attn_block.clear_cache()
    try:
        dense = np.asarray(reference.logprobs_of(params, tokens, sizes, 9))
    finally:
        monkeypatch.undo()
        reference._attn_block.clear_cache()
    assert np.abs(dense - real).max() > 1e-2


def test_the_control_moves_what_a_lower_precision_moves(tiny):
    _, params, sizes = tiny
    tokens = np.random.default_rng(3).integers(0, sizes["vocab"], 100)
    full = np.asarray(reference.logprobs_of(params, tokens, sizes, 9))
    low = np.asarray(reference.logprobs_of(params, tokens, sizes, 9,
                                           weights_as="float8_e4m3fn"))
    assert np.isfinite(low).all()
    assert 1e-3 < np.abs(low - full).max() < 5.0


# ---------------------------------------------------------------------------
# counts and readers
# ---------------------------------------------------------------------------

def test_counts_arithmetic(bench):
    sizes = builder.model_sizes(loader.config_of(bench, CONFIG))
    # a row of 21,000 tokens that keeps 2048: 4 layers of 64 x 2 B an index
    # key and 2 x 4 x 128 x 2 B a selected key
    assert sparse_attention.sparse_decode_bytes(21000, 2048, sizes) == (
        4 * (21000 * 128 + 2048 * 2048))
    assert sparse_attention.sparse_decode_flops(21000, 2048, sizes) == (
        4 * (2 * 16 * 64 * 21000 + 4 * 32 * 128 * 2048))
    assert moe_grouped.expert_bytes(sizes) == 3 * 2048 * 768 * 2
    assert moe_grouped.experts_touched(24 * 8, sizes) == pytest.approx(99.6, abs=0.1)
    assert moe_grouped.experts_touched(512 * 8, sizes) == pytest.approx(128, abs=1e-6)
    assert moe_grouped.grouped_flops(24, sizes) == 2 * 3 * 2048 * 768 * 192
    peaks = loader.peaks("TPU v5 lite")
    # decode is bound by the weights, a 512-token chunk still is (32 rows an expert)
    assert moe_grouped.least_seconds(24, sizes, peaks) == pytest.approx(
        moe_grouped.grouped_bytes(24, sizes) / peaks["hbm_bytes_per_s"])
    assert 1.1e-3 < moe_grouped.least_seconds(24, sizes, peaks) < 1.2e-3
    assert 1.4e-3 < moe_grouped.least_seconds(512, sizes, peaks) < 1.6e-3


def _run(sizes, sparse=True):
    def snap(sel, ctx, routed, computed):
        stats = {"decode": {"tokens": 0}, "moe": {"routed_rows": routed,
                                                  "computed_rows": computed}}
        if sparse:
            stats["sparse"] = {"selected_keys": sel, "context_keys": ctx}
            # (time of the sync, layer calls, experts touched): the first is
            # before the traced seconds
            stats["moe"]["recent_decode_syncs"] = [
                [9.0, 32, 32 * 90], [11.0, 32, 32 * 60], [12.0, 32, 32 * 50]]
        return {"stats": stats, "slots": 24}
    return {
        "counters": {"open": snap(1000, 9000, 50, 60),
                     "close": snap(1000 + 2048 * 24 * 8, 9000 + 20480 * 24 * 8,
                                   50 + 1000, 60 + 1100)},
        "trace": {"t0": 10.0, "t1": 14.0, "busy_s": 3.0, "devices": 1, "ops": {
            "sort_f32_24_29696_": [32, 0.02], "fusion_f32_24_29696_": [32, 0.03],
            "fusion_bf16_49152_4_128_": [64, 0.06], "gmm_bf16_192_768_": [64, 0.04],
            "gmm_bf16_4096_768_": [8, 0.01], "other": [5, 1.0]}},
        "engine": {"syncs": [[9.0, 24, 192, 8, 0.2, 24 * 20480],
                             [11.0, 24, 192, 8, 0.2, 24 * 20480]]},
        "sizes": sizes, "peaks": loader.peaks("TPU v5 lite")}


def test_new_readers_read_what_they_say(bench):
    sizes = builder.model_sizes(loader.config_of(bench, CONFIG))
    run = _run(sizes)
    assert group_ratio.read(run, {"num": "sparse.selected_keys",
                                  "den": "sparse.context_keys",
                                  "scale": 100}) == pytest.approx(10.0)
    assert group_ratio.read(run, {"num": "moe.computed_rows",
                                  "den": "moe.routed_rows"}) == pytest.approx(1.1)
    ops = [["sort_f32_24_"], ["fusion", "_24_29696_"], ["_49152_4_128_"]]
    assert ops_match.seconds_of(run["trace"]["ops"], ops) == (128, pytest.approx(0.11))
    assert ops_time_share.read(run, {"ops": ops}) == pytest.approx(100 * 0.11 / 3.0)
    assert ops_time_share.read(run, {"ops": [["no_such_op"]]}) is None
    # one sync of 8 steps inside the traced seconds
    need = 8 * sparse_attention.sparse_decode_bytes(
        24 * 20480, 24 * 2048, sizes)
    assert sparse_decode_roofline.read(run, {"ops": ops}) == pytest.approx(
        100 * need / 819e9 / 0.11)
    # 64 kernels of the trace at 2 a call are 32 layer calls, each reaching
    # the 55 experts that the syncs inside the traced seconds counted
    least = moe_grouped.least_seconds(24, sizes, run["peaks"], 55.0)
    assert least == pytest.approx(
        moe_grouped.grouped_bytes(24, sizes, 55.0) / 819e9)
    assert moe_grouped_roofline.read(
        run, {"ops": [["gmm", "_192_"]], "kernels_per_call": 2}
    ) == pytest.approx(100 * 32 * least / 0.04)


def test_new_readers_return_nothing_on_a_program_without_the_mechanism(bench):
    """As the parent's tree: no `sparse` group, no `moe` group, or no trace."""
    sizes = builder.model_sizes(loader.config_of(bench, CONFIG))
    run = _run(sizes, sparse=False)
    args = {"num": "sparse.selected_keys", "den": "sparse.context_keys"}
    assert group_ratio.read(run, args) is None
    assert sparse_decode_roofline.read(run, {"ops": [["sort"]]}) is None
    for side in ("open", "close"):
        del run["counters"][side]["stats"]["moe"]
    assert group_ratio.read(run, {"num": "moe.computed_rows",
                                  "den": "moe.routed_rows"}) is None
    assert moe_grouped_roofline.read(run, {"ops": [["gmm"]]}) is None
    run["trace"] = None
    assert ops_time_share.read(run, {"ops": [["sort"]]}) is None
    assert sparse_decode_roofline.read(_run(sizes) | {"trace": None},
                                       {"ops": [["sort"]]}) is None


# ---------------------------------------------------------------------------
# the rehearsal
# ---------------------------------------------------------------------------

def test_the_cell_rehearses_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_ARENA", "RAY_TPU_ADDRESS", "PYTHONPATH")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run(
        [sys.executable, os.path.join(loader.ROOT, "perfbench", "run.py"),
         "--rehearse", "--workload", CELL, "--seconds", "3"],
        env=env, cwd=loader.ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    plain, traced = [json.loads(ln) for ln in r.stdout.splitlines()
                     if ln.startswith("{")]
    for line in (plain, traced):
        assert line["rehearsal.agrees_with_reference_on_cpu"] is True
        assert line["rehearsal.failed"] == 0
    assert plain["rehearsal.out_tokens_per_s"] > 0
    share = traced["rehearsal.attn.selected_key_share.longdoc"]
    assert 5 < share < 40                  # topk 16 over contexts of 70-190
    assert traced["rehearsal.engine.prefix_hit_share.longdoc"] > 50
    assert 1.0 <= traced["rehearsal.moe.computed_rows_per_routed_row.longdoc"] < 3
    for name in NEW_METRICS:
        if not name.startswith(("kernel.", "attn.select_time", "step.")):
            assert math.isfinite(traced["rehearsal." + name]), name
