"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as NEW files plus entries in BENCHMARK.json, and edits no file that is
there. This test does exactly that in a temporary copy of the benchmark and
runs the harness on the new cell (CPU rehearsal)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import loader

ROOT = loader.ROOT
CELL = "mistral7b-chat"     # kept for later in PERF.md; not in BENCHMARK.json yet


def _copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "ray_tpu"), root / "ray_tpu")
    return root


def _add_a_cell(root):
    """New files only: a configuration, a mix, a metric with a reader of its
    own, and their entries."""
    bench_dir = root / "perfbench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    config = json.loads((bench_dir / "configs" / "mistral-7b-serve.json").read_text())
    config["name"] = "mistral-7b-serve-long"
    config["engine"]["max_seq_len"] = 4096
    (bench_dir / "configs" / "mistral-7b-serve-long.json").write_text(json.dumps(config))
    mix = json.loads((bench_dir / "traffic" / "chat.json").read_text())
    mix["rehearsal"]["rate_rps"] = 6.0
    (bench_dir / "traffic" / "chat-fast.json").write_text(json.dumps(mix))
    (bench_dir / "readers" / "requests_sent.py").write_text(
        "def read(run, args):\n    return len(run['requests']) * args['scale']\n")
    (bench_dir / "layer_metrics" / "gen.requests_sent.json").write_text(json.dumps({
        "name": "gen.requests_sent", "layer": "load generator", "unit": "requests",
        "better": "higher", "source": "program_counter", "moves": "ttft_p90_ms",
        "workloads": [CELL], "reader": "requests_sent.py",
        "args": {"scale": 1}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "mistral-7b-serve-long", "source": config["source"],
        "file": "perfbench/configs/mistral-7b-serve-long.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "mistral-7b-serve-long",
        "traffic": "chat-fast", "chips": 1, "why": "test"})
    # the latency metrics come with the first open-loop cell; the serving
    # metric files that are there already name the cell `mistral7b-chat`
    for name in ("ttft_p90_ms", "tpot_p50_ms"):
        bench["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.1,
            "source": "host_clock", "workloads": [CELL]})
    bench["per_layer"] = loader.per_layer_entries(bench, str(bench_dir))
    assert "client.ttft_p50_ms" in [m["name"] for m in bench["per_layer"]]
    bench["per_layer"].append({
        "name": "gen.requests_sent", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "ttft_p90_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    assert all(after[p] == data for p, data in before.items()), \
        "adding a cell edited a file that was there"
    return bench


def test_loader_finds_files_added_beside_the_old_ones(tmp_path):
    root = _copy(tmp_path)
    bench = _add_a_cell(root)
    here = str(root / "perfbench")
    cell = loader.cell(bench, CELL)
    config = loader.config_of(bench, cell["config"], str(root))
    assert config["engine"]["max_seq_len"] == 4096
    assert loader.traffic_of(cell["traffic"], here)["rehearsal"]["rate_rps"] == 6.0
    assert loader.layer_metric("gen.requests_sent", here)["reader"] == "requests_sent.py"
    names = [m["name"] for m in loader.metrics_of(bench, "per_layer", cell["name"])]
    assert "gen.requests_sent" in names and "client.ttft_p50_ms" in names
    assert "train.mfu" not in names


def test_harness_runs_a_cell_made_of_new_files_only(tmp_path):
    root = _copy(tmp_path)
    _add_a_cell(root)
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_ARENA", "RAY_TPU_ADDRESS", "PYTHONPATH")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run(
        [sys.executable, str(root / "perfbench" / "run.py"), "--rehearse",
         "--workload", CELL, "--seconds", "3"],
        env=env, cwd=str(root), capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert [ln["rehearsal.trace"] for ln in lines] == [0, 1]
    traced = lines[1]
    assert traced["rehearsal.gen.requests_sent"] > 0
    assert traced["rehearsal.agrees_with_reference_on_cpu"] is True
    # a rehearsal never prints a metric under its own name, nor `correct`
    for ln in lines:
        assert all(k.startswith("rehearsal.") for k in ln)
    assert '"correct"' not in r.stdout


def test_benchmark_json_matches_the_metric_files():
    bench = loader.benchmark()
    assert bench["per_layer"] == loader.per_layer_entries(bench)
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in ends
        spec = loader.layer_metric(m["name"])
        assert os.path.exists(os.path.join(loader.HERE, "readers", spec["reader"]))
    for w in bench["workloads"]:
        loader.config_of(bench, w["config"])
        traffic = loader.traffic_of(w["traffic"])
        assert hasattr(loader.module("generators", traffic["generator"]), "plan")
        assert len(loader.metrics_of(bench, "end_to_end", w["name"])) >= 2
        assert loader.metrics_of(bench, "per_layer", w["name"])


def test_an_unknown_device_kind_is_an_error():
    assert loader.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError):
        loader.peaks("TPU v9")


def test_train_mfu_is_the_needed_operations_over_the_steps_device_time():
    """From the trace, not from the traced run's wall clock (which holds the
    profiler's start and stop): the chip's own numbers of PR 23, 5 steps on 4
    chips at 599.05 ms of `jit_step` each, are 41.9%."""
    bench = loader.benchmark()
    config = loader.config_of(bench, "mistral-7b-fsdp4")
    sizes = loader.module("builders", config["builder"]).model_sizes(config)
    spec = loader.layer_metric("train.mfu")
    run = {"trace": {"modules": {"jit_step": [20, 20 * 0.5990495129]}},
           "peaks": loader.peaks("TPU v5 lite"), "chips": 4, "sizes": sizes,
           "train": {"tokens_per_step": 16384, "seq_len": 4096}}
    read = loader.module("readers", spec["reader"]).read
    assert read(run, spec["args"]) == pytest.approx(41.86, abs=0.01)
    assert read(dict(run, trace=None), spec["args"]) is None
