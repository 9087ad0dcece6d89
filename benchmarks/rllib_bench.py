"""RLlib throughput benches: env-steps/sec (BASELINE.json headline #2).

Run WITHOUT flags for the no-jax parent (bench.run_measure_child: one
`--measure` child under a timeout, its exit code propagated); `--measure` is
the real measurement child and names the `backend` it ran on. The learner
runs in that child, so on a chip host the child holds the chip; its env
runners and loopback nodes are CPU workers and never ask for it.

Two sections, selected by RLLIB_BENCH_SECTION:

  ppo (default) — {"ppo_env_steps_per_sec": N, ...}: PPO on CartPole for
    a fixed wall budget after one warmup iteration (compile excluded).
    RLLIB_BENCH_MULTINODE=0 skips the multinode section.

  sebulba — {"sebulba_env_steps_per_sec": N, "speedup_vs_sync": X, ...}:
    two-node CPU loopback, synchronous IMPALA (remote EnvRunner actors,
    SPREAD) vs the sebulba pipeline (device-resident rollout actors,
    ref-based replay, async learner). Asserts lockstep parity and
    pipeline.act/pipeline.learn span overlap in the SAME run.

`--smoke` is the tier-1 sebulba gate: single-host, asserts nonzero
fire-and-forget broadcasts, rollout/learn span overlap on the head
timeline, and sync-vs-lockstep weight parity.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the clusters this bench builds are CPU loopback: head and nodes advertise
# no chips (the one process that may hold a chip is the learner's, ours)
os.environ.setdefault("RAY_TPU_NUM_CHIPS", "0")


def main():
    import jax

    if os.environ.get("RLLIB_BENCH_SECTION", "ppo") == "sebulba":
        _sebulba_measure(float(os.environ.get("BUDGET_S", 15)))
        return

    from ray_tpu.rllib import PPOConfig

    config = (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                     rollout_fragment_length=64)
        .training(lr=3e-4, train_batch_size=256, minibatch_size=128,
                  num_epochs=2)
        .debugging(seed=0)
    )
    algo = config.build()
    algo.train()  # warmup: compiles the learner step

    iters = 0
    steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < float(os.environ.get("BUDGET_S", 15)):
        result = algo.train()
        iters += 1
        steps += int(result.get("num_env_steps_sampled_this_iter") or 256)
    dt = time.perf_counter() - t0
    algo.stop()
    record = {
        "ppo_env_steps_per_sec": round(steps / dt, 1),
        "iters": iters, "env_steps": steps,
        "backend": jax.default_backend(),
    }
    if os.environ.get("RLLIB_BENCH_MULTINODE", "1") != "0":
        try:
            record["multinode"] = _multinode(
                float(os.environ.get("BUDGET_S", 15)))
        except Exception as e:  # never sink the single-proc number
            record["multinode"] = {"error": f"{type(e).__name__}: {e}"}
    print(json.dumps(record))


def _multinode(budget_s):
    """BASELINE config #5 shape (VERDICT r4 next #7): EnvRunner actors
    SPREAD across head + one worker node feed the head learner. Records
    env-steps/s through the cluster plane and proves where runners ran."""
    import signal
    import subprocess

    import ray_tpu as ray
    from ray_tpu.rllib import PPOConfig

    ray.init(num_cpus=2, cluster_port=0)
    env = dict(os.environ)
    env.pop("RAY_TPU_ARENA", None)
    env.pop("RAY_TPU_ADDRESS", None)
    node = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_main",
         "--address", ray.cluster_address(), "--num-cpus", "2"],
        env=env, stdin=subprocess.DEVNULL, start_new_session=True)
    try:
        deadline = time.time() + 60
        while len(ray.nodes()) < 2 and time.time() < deadline:
            time.sleep(0.3)
        algo = (PPOConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                             rollout_fragment_length=64,
                             scheduling_strategy="SPREAD")
                .training(lr=3e-4, train_batch_size=256, minibatch_size=128,
                          num_epochs=2)
                .debugging(seed=0)
                .build())
        hosts = {i["ppid"] for i in ray.get(
            [r.node_info.remote() for r in algo._runner_handles],
            timeout=120)}
        algo.train()  # warmup
        iters = steps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < budget_s:
            result = algo.train()
            iters += 1
            steps += int(result.get("num_env_steps_sampled_this_iter") or 0)
        dt = time.perf_counter() - t0
        algo.stop()
        return {"ppo_env_steps_per_sec": round(steps / dt, 1),
                "iters": iters, "env_steps": steps,
                "runner_hosts": len(hosts), "nodes": len(ray.nodes())}
    finally:
        if node.poll() is None:
            os.killpg(node.pid, signal.SIGKILL)
            node.wait(timeout=10)
        ray.shutdown()


# ---------------------------------------------------------------- sebulba
def _enable_tracing():
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1.0"
    from ray_tpu.util import tracing
    tracing.refresh()
    return tracing


def _impala_base():
    from ray_tpu.rllib import IMPALAConfig
    return (IMPALAConfig()
            .environment("CartPole-v1")
            .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                         rollout_fragment_length=32)
            .training(train_batch_size=512)
            .debugging(seed=0))


def _parity_gap0(iters=2):
    """Same-run parity anchor: lockstep sebulba must reproduce the sync
    IMPALA schedule exactly (off-policy gap 0 → identical weights)."""
    import jax
    import numpy as np

    from ray_tpu.rllib import IMPALAConfig

    def cfg():
        return (IMPALAConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=0, num_envs_per_env_runner=2,
                             rollout_fragment_length=8)
                .training(train_batch_size=16)
                .debugging(seed=3))

    sync = cfg().build()
    for _ in range(iters):
        sync.train()
    w_sync = sync.get_weights()
    sync.stop()
    seb = cfg().sebulba(lockstep=True).build()
    for _ in range(iters):
        r = seb.train()
    gaps = r["sebulba"]["gap_counts"]
    w_seb = seb.get_weights()
    seb.stop()
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
              for a, b in zip(jax.tree_util.tree_leaves(w_sync),
                              jax.tree_util.tree_leaves(w_seb)))
    return {"iters": iters, "max_abs_err": err, "gap_counts": gaps,
            "ok": bool(err < 1e-5 and list(gaps) == [0])}


def _train_rate(algo, budget_s):
    """Measured env-steps/s over a wall budget, warmup iteration (jit
    compile) excluded."""
    algo.train()
    iters = steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        result = algo.train()
        iters += 1
        steps += int(result.get("num_env_steps_sampled_this_iter") or 0)
    dt = time.perf_counter() - t0
    return result, {"env_steps_per_sec": round(steps / dt, 1),
                    "iters": iters, "env_steps": steps,
                    "wall_s": round(dt, 2)}


def _sebulba_measure(budget_s):
    """Two-node CPU loopback: sync IMPALA (remote EnvRunner actors,
    SPREAD) vs the sebulba pipeline (device-resident rollout actors,
    ref-based replay, async V-trace learner). Parity and span overlap
    asserted in the same run; the speedup is the headline."""
    import signal
    import subprocess

    import jax

    tracing = _enable_tracing()
    import ray_tpu as ray
    from ray_tpu import api
    from ray_tpu._private.cluster import HEARTBEAT_S

    ray.init(num_cpus=3, cluster_port=0, resources={"head_node": 1.0})
    env = dict(os.environ)
    env.pop("RAY_TPU_ARENA", None)
    env.pop("RAY_TPU_ADDRESS", None)
    node = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node_main",
         "--address", ray.cluster_address(), "--num-cpus", "3",
         "--resources", '{"worker_node": 1}'],
        env=env, stdin=subprocess.DEVNULL, start_new_session=True)
    try:
        deadline = time.time() + 60
        while len(ray.nodes()) < 2 and time.time() < deadline:
            time.sleep(0.3)
        parity = _parity_gap0()

        sync_algo = (_impala_base()
                     .env_runners(num_env_runners=2,
                                  scheduling_strategy="SPREAD")
                     .build())
        sync_hosts = {i["ppid"] for i in ray.get(
            [r.node_info.remote() for r in sync_algo._runner_handles],
            timeout=120)}
        _, sync = _train_rate(sync_algo, budget_s / 2)
        sync_algo.stop()

        seb_algo = (_impala_base()
                    .env_runners(scheduling_strategy="SPREAD")
                    .sebulba(num_rollout_actors=2, inflight_rollouts=2,
                             replay_capacity=16, jax_env="cartpole")
                    .build())
        # ppid = the owning node agent: distinguishes loopback "nodes"
        seb_hosts = {i["ppid"] for i in ray.get(
            [a.node_info.remote() for a in seb_algo._sebulba.actors],
            timeout=120)}
        result, seb = _train_rate(seb_algo, budget_s / 2)
        stats = result["sebulba"]
        # worker-node spans reach the head timeline on heartbeats
        time.sleep(2 * HEARTBEAT_S + 0.5)
        events = api.timeline()
        overlap = tracing.overlap_stats(events, "pipeline.act",
                                        "pipeline.learn")
        seb_algo.stop()

        speedup = round(seb["env_steps_per_sec"]
                        / max(sync["env_steps_per_sec"], 1e-9), 2)
        record = {
            "bench": "rllib_sebulba", "backend": jax.default_backend(),
            "nodes": len(ray.nodes()),
            "sync": {**sync, "runner_hosts": len(sync_hosts)},
            "sebulba": {**seb, "actor_hosts": len(seb_hosts),
                        "updates": stats["updates"],
                        "broadcasts_async": stats["broadcasts_async"],
                        "gap_counts": stats["gap_counts"],
                        "jit_cache_size": stats["jit_cache_size"]},
            "sebulba_env_steps_per_sec": seb["env_steps_per_sec"],
            "sync_env_steps_per_sec": sync["env_steps_per_sec"],
            "speedup_vs_sync": speedup,
            "target_3x_met": bool(speedup >= 3.0),
            "parity": parity,
            "overlap": {k: round(v, 4) if isinstance(v, float) else v
                        for k, v in overlap.items()},
        }
        assert parity["ok"], record
        assert stats["broadcasts_async"] > 0, record
        assert stats["jit_cache_size"] == 1, record
        assert overlap["overlap_s"] > 0 and overlap["windows_a"] > 0, record
        print(json.dumps(record))
    finally:
        if node.poll() is None:
            os.killpg(node.pid, signal.SIGKILL)
            node.wait(timeout=10)
        ray.shutdown()


def smoke():
    """Tier-1 sebulba gate (single host, CPU): the async pipeline trains
    with nonzero fire-and-forget broadcasts, rollout (pipeline.act) and
    learn (pipeline.learn) spans OVERLAP on the head timeline, lockstep
    parity holds, and shutdown leaks nothing big."""
    tracing = _enable_tracing()
    import ray_tpu
    from ray_tpu import api
    from ray_tpu._private import state
    from ray_tpu._private.health import LeakDetector

    ray_tpu.init(num_cpus=4)
    try:
        parity = _parity_gap0()
        algo = (_impala_base()
                .env_runners(num_envs_per_env_runner=4,
                             rollout_fragment_length=16)
                .training(train_batch_size=128)
                .sebulba(num_rollout_actors=2, inflight_rollouts=2,
                         replay_capacity=8, jax_env="cartpole")
                .build())
        for _ in range(3):
            result = algo.train()
        stats = result["sebulba"]
        time.sleep(0.5)   # let shipped spans ride task_done to the head
        events = api.timeline()
        overlap = tracing.overlap_stats(events, "pipeline.act",
                                        "pipeline.learn")
        algo.stop()
        time.sleep(0.5)
        ctl = state.global_client().controller
        det = LeakDetector(age_s=0.0, clock=lambda: time.time() + 3600.0)
        big = [f for f in det.scan(ctl.objects)
               if (f.get("size") or 0) >= 1 << 16]
    finally:
        ray_tpu.shutdown()
    rec = {"bench": "rllib_sebulba_smoke", "smoke": "ok",
           "parity": parity,
           "updates": stats["updates"],
           "broadcasts_async": stats["broadcasts_async"],
           "gap_counts": stats["gap_counts"],
           "jit_cache_size": stats["jit_cache_size"],
           "act_windows": overlap["windows_a"],
           "learn_windows": overlap["windows_b"],
           "overlap_s": round(overlap["overlap_s"], 4),
           "overlap_fraction": round(overlap["overlap_fraction"], 4),
           "leaked_big": len(big)}
    assert parity["ok"], rec
    assert rec["broadcasts_async"] > 0, rec
    assert rec["jit_cache_size"] == 1, rec
    assert rec["act_windows"] > 0 and rec["learn_windows"] > 0, rec
    assert rec["overlap_s"] > 0, rec
    assert not big, rec
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--measure" in sys.argv[1:]:
        main()
    elif "--smoke" in sys.argv[1:]:
        smoke()
    else:
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
