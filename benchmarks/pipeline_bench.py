"""MPMD pipeline-parallel training benchmark (ISSUE 13 tentpole).

A 2+ stage llama-block pipeline where every stage is a `PipelineStage`
actor (`ray_tpu/train/mpmd.py`) placed by NodeAffinity round-robin over a
real two-host loopback cluster (this process is the head; a worker-node
agent subprocess is its own controller + shm arena). Activations and
grads hop between stages as object-store refs through the data plane, so
the dependency-prefetching dispatch overlaps each inter-stage hop with
the consuming stage's current compute.

Reported:
  * tokens/s over measured 1F1B steps (compile + warmup step excluded)
  * bubble fraction per stage worker from the PR 9 timeline — idle gaps
    between the stage methods' `exec` task-phase windows inside one
    measured step (`tracing.bubble_stats`, the same math behind
    `python -m ray_tpu timeline --bubble`) — vs the GPipe bound
    (S-1)/(M+S-1); 1F1B's worst stage should sit within ~1.5x of it
  * MPMD vs SPMD parity: the SAME stage_fn + params run through the
    single-program `parallel.pipeline.pipeline_apply` (mesh `pp` axis)
    must produce bitwise-identical forward outputs (CPU f32)
  * ref hygiene: live microbatch objects stay ~S in flight and the
    LeakDetector sees nothing big left pinned/unreleased after the run

Modes:
  --measure   real measurement child (run by bench.run_measure_child)
  --smoke     fast CPU gate (tier-1 test hook): single-host pipeline,
              MPMD forward bit-matches SPMD pipeline_apply, stage
              fwd/bwd windows + nonzero xfer windows on the head
              timeline, one 1F1B step trains without leaking
  (no flag)   parent: runs --measure once under a timeout and persists its
              record under benchmarks/results/

jax imports only happen in child modes; the stages are CPU workers (`tiny`
sizes), so what this reports is pipeline structure, not device speed.
"""

import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# head and loopback node advertise no chips: every stage is a CPU worker
os.environ.setdefault("RAY_TPU_NUM_CHIPS", "0")
# the driver runs the SPMD parity reference over a pp mesh of virtual
# host devices; workers inherit the flag harmlessly (each uses 1 device)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

STAGES = int(os.environ.get("RAY_TPU_PIPE_BENCH_STAGES", 2))
MICRO = int(os.environ.get("RAY_TPU_PIPE_BENCH_MICROBATCHES", 12))
STEPS = int(os.environ.get("RAY_TPU_PIPE_BENCH_STEPS", 3))
D_MODEL = int(os.environ.get("RAY_TPU_PIPE_BENCH_D_MODEL", 256))
SEQ = int(os.environ.get("RAY_TPU_PIPE_BENCH_SEQ", 128))
MB_BATCH = int(os.environ.get("RAY_TPU_PIPE_BENCH_MB_BATCH", 8))

# stage-method task names look like "<actor_id>.forward" (anonymous
# actors — naming them would exempt them from handle-drop GC), so trace
# filters select by method substring rather than a name prefix
_STAGE_METHODS = (".forward:", ".backward:", ".apply_grads:")


def _wait_for(pred, timeout, msg):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise TimeoutError("timed out waiting for " + msg)


class _Cluster:
    """Head in-process + one worker-node agent subprocess (the chain_bench
    shape). Stages round-robin over both nodes, so every inter-stage hop
    in a 2-stage pipeline crosses the loopback wire."""

    def __init__(self, head_cpus=3, node_cpus=3):
        import ray_tpu
        self.ray = ray_tpu
        ray_tpu.init(num_cpus=head_cpus, resources={"head_node": 1.0},
                     cluster_port=0)
        addr = ray_tpu.cluster_address()
        env = dict(os.environ)
        env.pop("RAY_TPU_ARENA", None)  # the node is its own session
        env.pop("RAY_TPU_ADDRESS", None)
        self.node = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_main",
             "--address", addr, "--num-cpus", str(node_cpus),
             "--resources", '{"worker_node": 1}'],
            env=env, stdin=subprocess.DEVNULL, start_new_session=True)
        _wait_for(lambda: len(ray_tpu.nodes()) == 2, 60, "node registration")

    def close(self):
        if self.node.poll() is None:
            os.killpg(self.node.pid, signal.SIGKILL)
            self.node.wait(timeout=10)
        self.ray.shutdown()


def _llama_stage(d_model):
    """One llama Block as the stage program: (params, x[B,T,D]) -> y, the
    inter-stage activation contract of both pipeline runners. f32 end to
    end so the MPMD-vs-SPMD comparison can be bitwise."""
    import jax.numpy as jnp
    from ray_tpu.models.llama import Block, LlamaConfig
    cfg = LlamaConfig.tiny(d_model=d_model, n_heads=4, n_kv_heads=2,
                           head_dim=d_model // 4, ffn_dim=4 * d_model,
                           max_seq_len=max(SEQ, 128),
                           dtype=jnp.float32, param_dtype=jnp.float32,
                           attn_impl="xla")
    blk = Block(cfg)

    def stage_fn(p, x):
        import jax.numpy as jnp  # runs inside stage workers too
        pos = jnp.arange(x.shape[1])[None, :].repeat(x.shape[0], 0)
        y, _ = blk.apply({"params": p}, x, pos, None)
        return y

    return cfg, blk, stage_fn


def _build_inputs(key, cfg, num_micro, mb_batch, seq):
    import jax
    import jax.numpy as jnp
    mbs = [jax.random.normal(jax.random.fold_in(key, 100 + m),
                             (mb_batch, seq, cfg.d_model), dtype=jnp.float32)
           for m in range(num_micro)]
    tgts = [jax.random.normal(jax.random.fold_in(key, 200 + m),
                              (mb_batch, seq, cfg.d_model),
                              dtype=jnp.float32) * 0.1
            for m in range(num_micro)]
    return mbs, tgts


def _stage_params(key, blk, cfg, num_stages, mb_batch, seq):
    import jax
    import jax.numpy as jnp
    x0 = jnp.zeros((mb_batch, seq, cfg.d_model), dtype=jnp.float32)
    pos = jnp.arange(seq)[None, :].repeat(mb_batch, 0)
    return [blk.init(jax.random.fold_in(key, i), x0, pos, None)["params"]
            for i in range(num_stages)]


def _spmd_reference(stage_fn, params, mbs):
    """Forward outputs from the single-program SPMD runner over a `pp`
    mesh — the parity baseline."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.pipeline import (pipeline_apply,
                                           shard_pipeline_params,
                                           stack_stage_params)
    S = len(params)
    mesh = make_mesh({"pp": S}, devices=jax.devices()[:S])
    stacked = shard_pipeline_params(stack_stage_params(params), mesh)
    return pipeline_apply(stage_fn, stacked, jnp.stack(mbs), mesh)


def _parity(outs, ref):
    import jax.numpy as jnp
    import numpy as np
    got = np.asarray(jnp.stack(outs))
    want = np.asarray(ref)
    return {"bitwise_equal": bool(np.array_equal(got, want)),
            "max_abs_diff": float(np.max(np.abs(got - want)))}


def _loss_fn(y, t):
    import jax.numpy as jnp
    return jnp.mean((y - t) ** 2)


def _leak_scan(min_bytes=1 << 20):
    """LeakDetector view of the head object table: anything big still
    pinned/unreleased after the run is a pipeline ref-lifecycle bug."""
    from ray_tpu._private import state
    from ray_tpu._private.health import LeakDetector
    ctl = state.global_client().controller
    det = LeakDetector(age_s=0.0)
    flagged = det.scan(ctl.objects, now=time.time() + 3600.0)
    return {"tracked_objects": len(ctl.objects), "flagged": len(flagged),
            "flagged_big": [f for f in flagged
                            if (f.get("size") or 0) >= min_bytes]}


def _pipeline_run(num_stages, num_micro, steps, warmup=True):
    """Build the stage actors, run 1F1B steps, return everything the
    record needs. Caller owns session/cluster setup + teardown."""
    import jax
    from ray_tpu.train.mpmd import build_pipeline, sgd
    cfg, blk, stage_fn = _llama_stage(D_MODEL)
    key = jax.random.PRNGKey(0)
    params = _stage_params(key, blk, cfg, num_stages, MB_BATCH, SEQ)
    mbs, tgts = _build_inputs(key, cfg, num_micro, MB_BATCH, SEQ)

    pipe = build_pipeline([stage_fn] * num_stages, params,
                          loss_fn=_loss_fn, optimizer=sgd(0.05))

    # parity BEFORE training mutates the params: the same stage_fn +
    # params through the SPMD runner must match bitwise
    outs = pipe.run_forward(mbs)
    parity = _parity(outs, _spmd_reference(stage_fn, params, mbs))
    del outs

    if warmup:  # compile fwd+bwd+apply on every stage outside the window
        pipe.train_step(mbs, tgts)
    losses, step_marks = [], []
    t0 = time.perf_counter()
    for _ in range(steps):
        t_a = time.time()
        losses.append(pipe.train_step(mbs, tgts)["loss"])
        step_marks.append((t_a, time.time()))
    wall = time.perf_counter() - t0
    tokens = steps * num_micro * MB_BATCH * SEQ
    return {"pipe": pipe, "parity": parity, "losses": losses,
            "wall_s": wall, "tokens_per_s": tokens / max(wall, 1e-9),
            "step_marks": step_marks, "stats": pipe.last_stats,
            "cfg": {"stages": num_stages, "microbatches": num_micro,
                    "steps": steps, "d_model": D_MODEL, "seq": SEQ,
                    "mb_batch": MB_BATCH}}


def _stage_exec_events(events):
    return [e for e in events
            if e.get("cat") == "task_phase"
            and any(s in str(e.get("name", "")) for s in _STAGE_METHODS)]


def _bubble_report(events, step_marks, num_stages, num_micro):
    """Bubble fractions from the stage methods' exec-phase windows inside
    the LAST measured step (one full 1F1B schedule, no step-boundary
    driver barrier inside it); worst stage vs the GPipe bound."""
    from ray_tpu.util import tracing
    t_a, t_b = step_marks[-1]
    window = [e for e in _stage_exec_events(events)
              if t_a <= e.get("ts", 0) / 1e6 <= t_b + 1.0]
    stats = tracing.bubble_stats(window)
    fracs = [w["bubble_fraction"] for w in stats["workers"].values()]
    bound = (num_stages - 1) / (num_micro + num_stages - 1)
    worst = max(fracs) if fracs else None
    return {"per_worker": {str(k): round(v["bubble_fraction"], 4)
                           for k, v in stats["workers"].items()},
            "exec_windows": sum(w["windows"]
                                for w in stats["workers"].values()),
            "bubble_fraction": worst,
            "gpipe_bound": round(bound, 4),
            "vs_bound": (round(worst / bound, 3)
                         if fracs and bound > 0 else None)}


def measure():
    import jax
    from bench import observability_snapshot
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1.0"
    from ray_tpu.util import tracing
    tracing.refresh()
    from ray_tpu import api
    from ray_tpu._private.cluster import HEARTBEAT_S
    t_begin = time.time()
    cl = _Cluster()
    try:
        run = _pipeline_run(STAGES, MICRO, STEPS)
        run["pipe"].shutdown()
        # worker-node task_phase windows reach the head on heartbeats
        time.sleep(2 * HEARTBEAT_S + 0.5)
        events = api.timeline()
        bubble = _bubble_report(events, run["step_marks"], STAGES, MICRO)
        time.sleep(0.5)  # let actor teardown / unpins settle
        leaks = _leak_scan()
    finally:
        cl.close()
    rec = {"bench": "pipeline_pp", "backend": jax.default_backend(),
           **run["cfg"],
           "tokens_per_s": round(run["tokens_per_s"], 1),
           "wall_s": round(run["wall_s"], 3),
           "losses": [round(l, 6) for l in run["losses"]],
           "parity": run["parity"], "bubble": bubble,
           "schedule": {"peak_live_refs": run["stats"]["peak_live_refs"],
                        "ops_submitted": run["stats"]["ops_submitted"]},
           "leak_scan": leaks,
           "nodes": 2, "t_total_s": round(time.time() - t_begin, 1),
           "observability": observability_snapshot()}
    assert rec["parity"]["bitwise_equal"], rec
    assert not leaks["flagged_big"], rec
    print(json.dumps(rec))


def smoke():
    """Tier-1 gate: single-host CPU pipeline (stage actors are separate
    worker processes, so the object-plane hops and trace plumbing are the
    real thing) — MPMD forward bit-matches SPMD `pipeline_apply`, stage
    fwd/bwd windows and nonzero xfer phase windows reach the head
    timeline, and one 1F1B step trains and leaks nothing."""
    global D_MODEL, SEQ, MB_BATCH
    D_MODEL, SEQ, MB_BATCH = 64, 32, 2
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1.0"
    import ray_tpu
    from ray_tpu import api
    from ray_tpu.util import tracing
    tracing.refresh()
    ray_tpu.init(num_cpus=4)
    try:
        run = _pipeline_run(num_stages=2, num_micro=8, steps=1,
                            warmup=False)
        run["pipe"].shutdown()
        events = api.timeline()
        fwd = [e for e in events if e.get("name") == "pipeline.fwd"]
        bwd = [e for e in events if e.get("name") == "pipeline.bwd"]
        xfer = [e for e in _stage_exec_events(events)
                if (e.get("args") or {}).get("phase") == "xfer"
                and e.get("dur", 0) > 0]
        time.sleep(0.5)
        leaks = _leak_scan(min_bytes=64 * 1024)
    finally:
        ray_tpu.shutdown()
    rec = {"bench": "pipeline_pp_smoke", "smoke": "ok",
           "parity": run["parity"],
           "loss": round(run["losses"][0], 6),
           "fwd_windows": len(fwd), "bwd_windows": len(bwd),
           "xfer_windows": len(xfer),
           "peak_live_refs": run["stats"]["peak_live_refs"],
           "leak_scan": {k: leaks[k] for k in ("tracked_objects",
                                               "flagged_big")}}
    assert rec["parity"]["bitwise_equal"], rec
    # every stage ships its windows: 2 stages x (8 parity fwd + 8 train
    # fwd) and 2 x 8 bwd; xfer phases exist for the stage-method tasks
    assert rec["fwd_windows"] >= 16 and rec["bwd_windows"] >= 8, rec
    assert rec["xfer_windows"] > 0, rec
    assert not leaks["flagged_big"], rec
    assert rec["peak_live_refs"] <= 2 * 2 + 2, rec  # ~S in flight
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--measure" in sys.argv[1:]:
        measure()
    elif "--smoke" in sys.argv[1:]:
        smoke()
    else:
        # parent mode: one --measure child under a timeout, its record
        # persisted, its exit code ours
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
