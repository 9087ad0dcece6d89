"""`falcon-h1-34b-serve` and `falconh1-syschat-batch`: the loader finds every
file by name, the configuration's file holds the catalog's keys, the weights'
scales in it are the builder's rule at the published widths, `counts/ssd.py`
against hand-worked numbers, the manifest equals the metric files by name, and
the cell's control flow runs on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import loader
from perfbench.builders import falcon_h1 as builder
from perfbench.counts import ssd
from perfbench.references import falcon_h1 as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "falconh1-syschat-batch", "falcon-h1-34b-serve"
METRICS = [
    "kernel.ssd_decode_roofline", "kernel.ssd_prefill_roofline",
    "attn.ssd_time_share", "kernel.paged_decode_roofline",
    "state.branch_hit_share", "step.decode_ms", "step.prefill_chunk_ms",
    "engine.batch_occupancy_mean", "engine.decode_tokens_per_sync",
    "engine.prefix_hit_share", "engine.stall_share",
    "state.resume_gap_tokens_per_hit", "wall.decode_sync_share",
    "wall.yield_share", "wall.state_copy_share", "setup.compile_s",
    "setup.actor_ready_s"]
# Falcon-H1-34B-Instruct's config.json as the catalog has it
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


@pytest.fixture(scope="module")
def bench():
    return loader.benchmark()


def test_loader_finds_the_cell_and_all_it_is_made_of(bench):
    cell = loader.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "syschat", 1)
    config = loader.config_of(bench, CONFIG)
    assert loader.module("builders", config["builder"]) is builder
    assert loader.reference_of(config) is reference
    sizes = builder.model_sizes(config)
    assert set(loader.check_of(config, sizes)) == {"median_tol", "far",
                                                  "far_share"}
    assert len(config["check"]["why"]) > 100
    traffic = loader.traffic_of(cell["traffic"])
    assert traffic["generator"] == "shared_backlog.py"
    assert hasattr(loader.module("generators", traffic["generator"]), "plan")
    ends = {m["name"] for m in loader.metrics_of(bench, "end_to_end", CELL)}
    assert ends == {"out_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in loader.metrics_of(bench, "per_layer", CELL)}
    assert layer == {m + ".syschat" for m in METRICS}
    for name in layer:
        spec = loader.layer_metric(name)
        assert spec["workloads"] == [CELL]
        assert hasattr(loader.module("readers", spec["reader"]), "read")


def test_the_manifest_is_the_metric_files_by_name(bench):
    printed = json.loads(subprocess.check_output(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--print-per-layer"]))
    assert ([m["name"] for m in printed]
            == [m["name"] for m in bench["per_layer"]])
    assert printed == bench["per_layer"]


def test_the_file_holds_the_published_keys_and_cuts_depth_alone(bench):
    config = loader.config_of(bench, CONFIG)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == list(config["reduced"])
    for key, value in PUBLISHED.items():
        assert config[key] == (5 if key == "num_hidden_layers" else value), key
    assert config["reduced"]["num_hidden_layers"]["source"] == 72
    for group in ("deployment", "assumed", "bytes_on_chip", "engine"):
        assert config[group]
    sizes = builder.model_sizes(config)
    per_slot = ssd.state_bytes_per_slot(sizes)
    held = config["bytes_on_chip"]
    assert per_slot == held["state_bytes_per_slot"] == 21_125_120
    assert held["slot_state"] == 96 * per_slot
    assert held["snapshot_pool"] == config["engine"]["num_snapshots"] * per_slot
    assert held["kv_pool"] == (config["engine"]["num_pages"] * 64
                               * held["kv_bytes_per_token"])
    assert held["kv_bytes_per_token"] == 5 * 2 * 4 * 128 * 2


def test_the_weights_scales_are_the_rule_at_the_published_widths(bench):
    config = loader.config_of(bench, CONFIG)
    rule = builder.weight_scales(builder.model_sizes(config))
    given = config["assumed"]["weight_scale"]["values"]
    assert set(rule) == set(given)
    for name, value in rule.items():
        assert given[name] == pytest.approx(value, rel=1e-5), name


def test_a_checkout_without_the_mixer_is_refused_at_once(bench, monkeypatch):
    monkeypatch.setattr(builder, "_PROGRAM", "/nowhere/ssd.py")
    with pytest.raises(SystemExit, match="no state-space mixer"):
        builder.model_sizes(loader.config_of(bench, CONFIG))


def test_counts_against_hand_worked_numbers(bench):
    s = builder.model_sizes(loader.config_of(bench, CONFIG))
    assert ssd.mixer_layers(s) == 5
    assert ssd.state_bytes(s) == 32 * 256 * 128 * 4 == 4_194_304
    assert ssd.conv_bytes(s) == 3 * 5120 * 2 == 30_720
    # 96 live rows, five layers, read and written: 4.03 GB a step
    assert ssd.decode_bytes(96, s) == 96 * 5 * 2 * 4_194_304 == 4_026_531_840
    # a chunk of 128 tokens: C B^T in 2 groups 128^2 x 256 each; a head
    # 128^2 x 128 and 4 x 128 x 256 x 128
    chunk = 2 * 128 * 128 * 256 + 32 * (128 * 128 * 128 + 4 * 128 * 256 * 128)
    assert chunk == 612_368_384
    assert ssd.chunked_flops(128, s) == chunk
    assert ssd.chunked_flops(1024, s) == 8 * chunk
    assert ssd.chunked_flops(130, s) == 2 * chunk
    per_token = 32 * 128 * 2 + 2 * 2 * 256 * 2 + 32 * 4 + 32 * 128 * 4
    assert per_token == 26_752
    assert ssd.chunked_bytes(1024, s) == 1024 * per_token + 2 * 4_194_304
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # memory-bound: 35.8 MB at 819 GB/s is 43.7 us, 4.9 GFLOP is 24.9 us
    assert ssd.chunked_least_seconds(1024, s, peaks) == pytest.approx(
        35_782_656 / 819e9)
    assert ssd.mixer_layers({"n_layers": 4}) == 0


def test_the_mix_is_what_the_issue_gives(bench):
    mix = loader.traffic_of("syschat")
    assert (mix["in_flight"], mix["recent"], mix["block"],
            mix["new_per_block"], mix["settle_requests"],
            mix["requests_per_second_ceiling"]) == (144, 8, 64, 1, 160, 32)
    assert mix["document"] == {"median": 1536, "sigma": 0.25, "min": 1024,
                               "max": 2560}
    assert mix["question"] == {"min": 32, "max": 640}
    assert mix["output"] == {"median": 192, "sigma": 0.7, "min": 32,
                             "max": 768}
    assert mix["ramp"] == {"requests": 96, "output_min": 32,
                           "output_max": 256}
    sizes = builder.model_sizes(loader.config_of(bench, CONFIG))
    plan = loader.module("generators", mix["generator"]).plan(
        mix, 5, 45.0, sizes)
    kinds = [r["kind"] for r in plan["requests"]]
    assert kinds[:96] == ["ramp"] * 96 and len(plan["setup"]) == 8
    assert kinds.count("miss") == sum(
        i % 64 == 32 for i in range(len(kinds) - 96))      # one a block
    longest = max(len(r["prompt"]) + r["max_tokens"] for r in plan["requests"])
    engine = loader.config_of(bench, CONFIG)["engine"]
    assert longest <= engine["max_seq_len"]


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_ARENA", "RAY_TPU_ADDRESS", "PYTHONPATH")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--rehearse", "--workload", CELL, "--seconds", "3"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert [ln["rehearsal.trace"] for ln in lines] == [0, 1]
    traced = lines[1]
    assert traced["rehearsal.agrees_with_reference_on_cpu"] is True
    assert traced["rehearsal.failed"] == 0
    assert traced["rehearsal.state.branch_hit_share.syschat"] > 0
    for ln in lines:
        assert all(k.startswith("rehearsal.") for k in ln)
