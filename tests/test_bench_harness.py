"""bench.py harness: a jax-free orchestrator around measurement children.

What is held here, without TPU hardware:
  (a) every child runs under a hard timeout that kills its whole process
      group (a chip held by a dead grandchild is real on libtpu),
  (b) a measure child that finds no chip exits non-zero and prints no
      record, and the orchestrator propagates that failure — no CPU number
      is ever produced under a device metric's name,
  (c) the stale sweep recognizes node_main / stray bench processes,
  (d) orchestrate emits the train JSON line before aux benches run,
  (e) the compile cache lives where JAX_COMPILATION_CACHE_DIR says, else at
      one fixed in-checkout path.

Ref contrast: /root/reference/release/benchmarks wraps each workload in hard
timeouts; its run_release_test.py kills the whole anyscale job on overrun.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _no_artifacts(monkeypatch):
    # no test here may litter benchmarks/results/ — the artifact tests
    # opt back in against a tmp_path RESULTS_DIR
    monkeypatch.setenv("RAY_TPU_BENCH_WRITE_RESULTS", "0")
    yield


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_BENCH_WRITE_RESULTS="0")
    env.update(extra)
    return env


# ------------------------------------------------------------ child watching

def test_run_watched_kills_child_at_timeout():
    t0 = time.monotonic()
    rc, out, err, reason = bench._run_watched(
        [sys.executable, "-c", "import time; time.sleep(600)"],
        dict(os.environ), timeout=2)
    assert reason == "timeout" and rc != 0
    assert time.monotonic() - t0 < 30


def test_run_watched_kills_the_whole_process_group(tmp_path):
    """The grandchild is the process that would be holding the chip."""
    pidfile = tmp_path / "grandchild.pid"
    code = ("import subprocess, sys, time\n"
            "p = subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(600)'])\n"
            f"open({str(pidfile)!r}, 'w').write(str(p.pid))\n"
            "time.sleep(600)\n")
    _, _, _, reason = bench._run_watched([sys.executable, "-c", code],
                                         dict(os.environ), timeout=5)
    assert reason == "timeout"
    pid = int(pidfile.read_text())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        os.kill(pid, 9)
        pytest.fail("grandchild survived the timeout kill")


def test_run_watched_passes_healthy_child():
    rc, out, err, reason = bench._run_watched(
        [sys.executable, "-c", "print('{\"ok\": 1}')"], dict(os.environ),
        timeout=60)
    assert reason is None and rc == 0
    assert bench._parse_json_tail(out) == {"ok": 1}


# ------------------------------------------------------ no chip, no number

def test_measure_child_without_a_chip_exits_nonzero():
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "--measure"], env=_cpu_env(), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert bench._parse_json_tail(r.stdout) is None, r.stdout
    assert "needs a TPU" in r.stderr


def test_orchestrator_propagates_a_chipless_child():
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=_cpu_env(RAY_TPU_BENCH_TRAIN_ONLY="1"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert bench._parse_json_tail(r.stdout) is None, r.stdout


def test_run_child_exits_on_timeout(monkeypatch):
    monkeypatch.setattr(bench, "_run_watched",
                        lambda cmd, env, timeout: (-9, "", "", "timeout"))
    with pytest.raises(SystemExit) as e:
        bench._run_child()
    assert e.value.code not in (0, None)


def test_run_child_exits_when_the_child_prints_no_record(monkeypatch):
    monkeypatch.setattr(bench, "_run_watched",
                        lambda cmd, env, timeout: (0, "no json here\n", "", None))
    with pytest.raises(SystemExit) as e:
        bench._run_child()
    assert e.value.code not in (0, None)


def test_run_child_gives_the_child_its_hard_timeout(monkeypatch):
    seen = {}

    def spy(cmd, env, timeout):
        seen.update(cmd=cmd, timeout=timeout)
        return 0, '{"metric": "m", "value": 1.0}\n', "", None

    monkeypatch.setattr(bench, "_run_watched", spy)
    assert bench._run_child() == {"metric": "m", "value": 1.0}
    assert seen["timeout"] == bench.MEASURE_TIMEOUT_S
    assert seen["cmd"][-1] == "--measure"


# ---------------------------------------------------------------- the sweep

def test_stale_sweep_matches_node_and_bench_processes():
    """_kill_stale_workers kills a node_main whose head is gone and a stray
    --measure child."""
    # fake node_main: argv contains the module name + a dead head address
    node = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, time; time.sleep(300)",
         "ray_tpu._private.node_main", "--address", "127.0.0.1:1"],
        start_new_session=True)
    # fake stray measure child from a killed previous run
    stray = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(300)",
         "bench.py", "--measure"],
        start_new_session=True)
    try:
        deadline = time.monotonic() + 30
        bench._kill_stale_workers()
        while time.monotonic() < deadline:
            if node.poll() is not None and stray.poll() is not None:
                break
            time.sleep(0.2)
        assert node.poll() is not None, "stale node_main survived the sweep"
        assert stray.poll() is not None, "stray --measure child survived"
    finally:
        for p in (node, stray):
            if p.poll() is None:
                p.kill()
            p.wait()


# ------------------------------------------------------------- orchestration

def test_orchestrate_emits_train_line_before_aux(monkeypatch, capsys):
    """The headline JSON must hit stdout before any aux bench runs, and the
    merged record is the final line (a kill during aux must not lose the
    measured number)."""
    order = []

    monkeypatch.setattr(bench, "_kill_stale_workers", lambda: None)
    monkeypatch.setattr(bench, "_sweep_orphan_shm", lambda: None)
    monkeypatch.setattr(bench, "_run_child",
                        lambda: {"metric": "m", "value": 2.0})

    def fake_aux(script, timeout, env_extra=None):
        order.append(script)
        return {"ok": script}

    monkeypatch.setattr(bench, "_run_aux_bench", fake_aux)
    monkeypatch.delenv("RAY_TPU_BENCH_TRAIN_ONLY", raising=False)
    bench.orchestrate()
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    # first line: train headline, already valid
    assert lines[0] == {"metric": "m", "value": 2.0}
    # aux results re-emit the merged record, full record last
    assert lines[-1]["serving_b8"] == {"ok": "serving_bench.py"}
    assert lines[-1]["serving_b32"] == {"ok": "serving_bench.py"}
    assert lines[-1]["rllib_ppo"] == {"ok": "rllib_bench.py"}


def test_orchestrate_train_only_prints_one_record(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_kill_stale_workers", lambda: None)
    monkeypatch.setattr(bench, "_sweep_orphan_shm", lambda: None)
    monkeypatch.setattr(bench, "_run_child",
                        lambda: {"metric": "m", "value": 2.0})
    monkeypatch.setattr(bench, "_run_aux_bench", lambda *a, **k: pytest.fail(
        "aux benches must not run under RAY_TPU_BENCH_TRAIN_ONLY"))
    monkeypatch.setenv("RAY_TPU_BENCH_TRAIN_ONLY", "1")
    bench.orchestrate()
    assert capsys.readouterr().out.strip().splitlines() == [
        json.dumps({"metric": "m", "value": 2.0})]


# ---------------------------------------------------------------- artifacts

def test_write_result_artifact_roundtrip(tmp_path, monkeypatch):
    """Successful records persist as <tag>_<UTC ts>.json under the results
    dir: perf claims become committed, diffable artifacts."""
    monkeypatch.setenv("RAY_TPU_BENCH_RESULTS_DIR", str(tmp_path))
    monkeypatch.delenv("RAY_TPU_BENCH_WRITE_RESULTS", raising=False)
    rec = {"metric": "train_tok_s", "value": 123.4}
    path = bench._write_result_artifact("llama_1b", rec)
    assert path is not None and os.path.dirname(path) == str(tmp_path)
    name = os.path.basename(path)
    assert name.startswith("llama_1b_") and name.endswith(".json")
    with open(path) as f:
        assert json.load(f) == rec


def test_write_result_artifact_kill_switch(tmp_path, monkeypatch):
    """RAY_TPU_BENCH_WRITE_RESULTS=0 disables writes — tests that spawn
    real children rely on this to keep the repo clean."""
    monkeypatch.setenv("RAY_TPU_BENCH_RESULTS_DIR", str(tmp_path))
    monkeypatch.setenv("RAY_TPU_BENCH_WRITE_RESULTS", "0")
    assert bench._write_result_artifact("x", {"v": 1}) is None
    assert not list(tmp_path.iterdir())


def test_run_child_writes_artifact_on_success(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_BENCH_RESULTS_DIR", str(tmp_path))
    monkeypatch.delenv("RAY_TPU_BENCH_WRITE_RESULTS", raising=False)
    monkeypatch.setattr(
        bench, "_run_watched",
        lambda cmd, env, timeout: (0, '{"metric": "m", "value": 2.0}\n', "", None))
    assert bench._run_child() is not None
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 1 and files[0].startswith("llama_1b_")


# --------------------------------------------------- aux benches' parent mode

def test_run_measure_child_prints_and_persists_the_record(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("RAY_TPU_BENCH_RESULTS_DIR", str(tmp_path))
    monkeypatch.delenv("RAY_TPU_BENCH_WRITE_RESULTS", raising=False)
    calls = []

    def fake(cmd, env, timeout):
        calls.append(cmd)
        return 0, 'noise\n{"dense": {"decode_tps": 9.0}, "backend": "tpu"}\n', "", None

    monkeypatch.setattr(bench, "_run_watched", fake)
    assert bench.run_measure_child("/x/serving_bench.py") == 0
    assert calls == [[sys.executable, "/x/serving_bench.py", "--measure"]]
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec == {"dense": {"decode_tps": 9.0}, "backend": "tpu"}
    files = os.listdir(tmp_path)
    assert len(files) == 1 and files[0].startswith("serving_bench_")


def test_run_measure_child_propagates_the_childs_failure(monkeypatch, capsys):
    """One child, once: a failed measurement fails the parent with the
    child's code and nothing reruns it on another backend."""
    calls = []

    def fake(cmd, env, timeout):
        calls.append(env.get("JAX_PLATFORMS"))
        return 7, "", "boom", None

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(bench, "_run_watched", fake)
    assert bench.run_measure_child("/x/rllib_bench.py") == 7
    assert calls == ["tpu,cpu"]      # the caller's env, exactly once
    assert capsys.readouterr().out.strip() == ""


def test_run_measure_child_fails_on_timeout(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_run_watched",
                        lambda cmd, env, timeout: (-9, "", "", "timeout"))
    assert bench.run_measure_child("/x/serving_bench.py") != 0
    assert capsys.readouterr().out.strip() == ""


# ------------------------------------------------------------- compile cache

def test_compile_cache_dir_follows_the_env_var(monkeypatch, tmp_path):
    from ray_tpu.util import tpu
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert tpu.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_is_one_fixed_path_in_the_checkout(monkeypatch):
    from ray_tpu.util import tpu
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert tpu.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("TMPDIR", "/somewhere/else")  # nothing temp-derived
    assert tpu.compile_cache_dir() == os.path.join(REPO, ".jax_cache")


def test_measure_places_the_cache_before_it_needs_a_chip(monkeypatch):
    """--measure defaults the cache dir (so a later chip run of the same
    checkout finds what this one compiled) and never overrides one given."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with pytest.raises(SystemExit):
        bench.measure()          # this process is CPU-only: exits, no record
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/given")
    with pytest.raises(SystemExit):
        bench.measure()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/given"


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    from ray_tpu.util import tpu
    v5e = tpu.chip_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        tpu.chip_peaks("TPU v99")
    with pytest.raises(ValueError):
        tpu.chip_peaks("cpu")


@pytest.mark.slow
def test_serving_bench_parent_runs_its_measure_child():
    """serving_bench.py WITHOUT flags: the jax-free parent runs --measure
    once and hands its record and exit code through (CPU box: the record
    says backend=cpu; it is a correctness run, not a measurement)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "serving_bench.py")],
        env=_cpu_env(B="2", MAX_TOKENS="4", PROMPT_LEN="8", ROUNDS="1",
                     SECTIONS="dense"),
        capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = bench._parse_json_tail(r.stdout)
    assert rec is not None, r.stdout[-500:]
    assert rec["backend"] == "cpu"
    assert rec["dense"]["host_syncs_per_token"] <= 1.0
