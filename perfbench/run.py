"""perfbench/run.py: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent process (this file) never imports jax: a chip belongs to the one
process that opened it. All device work happens in chip-bound actors reached
through the normal entry points (`serve.run` of a `num_tpus=1` deployment that
owns an `LLMServer`; `train.JaxTrainer` with `ScalingConfig(use_tpu=True,
chips_per_worker=N)`), and the profiler runs inside the actor that holds the
chip. A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`). With `--trace 0` the metrics are the cell's end-to-end metrics,
with `--trace 1` its per-layer metrics. Everything else goes on earlier lines
(prefixed `[perfbench]`) or under `perfbench_out/`.

Other modes, for builders (see README.md): `--rehearse` (CPU, tiny sizes,
numbers under `rehearsal.*` names only), `--sweep r1,r2,..` (the knee sweep of
an open-loop cell), `--print-per-layer` (BENCHMARK.json's `per_layer` list as
the metric files give it), `--control-dtype` (the tolerances' control).
"""

import argparse
import json
import math
import os
import signal
import sys
import time

T_PROCESS = time.monotonic()    # set-up is counted from here

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
# chip-bound workers import perfbench.* and ray_tpu from the same checkout
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
# the program's sockets, sessions and spill files go under TMPDIR, which the
# driver gives each side of a comparison for itself
os.environ.pop("XDG_RUNTIME_DIR", None)

from perfbench import client as client_mod  # noqa: E402
from perfbench import loader, stats  # noqa: E402

_now = time.monotonic
ACTOR_TIMEOUT_S = 1100.0     # a cold first run compiles for minutes
DEADLINE_S = 1180            # the contract's limit for a first run is 1200

# Tolerances of `correct`: the serving comparison and its reasons are
# `stats.logprobs_agree`.
# Training: the mean loss over 16,384 tokens in bf16 activations against f32;
# measured 4e-5 to 3e-4 apart at a loss of 11.2 (6 runs, my chip runs, PR 23).
# bf16 master weights or a dropped layer move it by 1e-2.
LOSS_TOL = 3e-3


def say(what: str, facts) -> None:
    print(f"[perfbench] {what}: {json.dumps(facts, default=str)}", flush=True)


def _deadline(_signum, _frame):
    raise TimeoutError(f"perfbench run exceeded {DEADLINE_S} s")


# --------------------------------------------------------------------------
# reading per-layer metrics and building the line
# --------------------------------------------------------------------------

def layer_values(bench: dict, workload: str, run: dict) -> dict:
    """Each per-layer metric of the cell through its reader file. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in loader.metrics_of(bench, "per_layer", workload):
        spec = loader.layer_metric(m["name"])
        value = loader.module("readers", spec["reader"]).read(
            run, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _peaks(args, device: dict):
    """The chip's published peaks; a rehearsal on the CPU has none."""
    return None if args.rehearse else loader.peaks(device["kind"])


def result_line(bench, workload, trace, run, end_to_end, correct, attempted,
                failed) -> dict:
    device = dict(run["device"], memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if trace:
        tr = run["trace"]
        line["metrics"] = layer_values(bench, workload, run)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in end_to_end.items()}
    line["device"] = device
    return line


# --------------------------------------------------------------------------
# serving cells
# --------------------------------------------------------------------------

def _call(handle, method: str, *args, timeout_s: float = ACTOR_TIMEOUT_S):
    return getattr(handle, method).remote(*args).result(timeout_s=timeout_s)


def serve_window(handle, plan: dict, seconds: float, trace: bool) -> dict:
    """One measured window of a serving cell; returns the run's records."""
    import threading
    client = client_mod.Client(handle)
    tracing = {}

    def trace_window(t_open):
        # the window's last seconds, from a thread of its own so that the
        # sender is never held up. Stopping the profiler stalls the replica
        # for many seconds, so it comes when every request has been sent.
        def body():
            time.sleep(max(0.0, t_open + seconds - min(4.0, 0.5 * seconds) - _now()))
            _call(handle, "start_trace")
            time.sleep(max(0.0, t_open + seconds - 0.2 - _now()))
            _call(handle, "stop_trace")
        t = threading.Thread(target=body, daemon=True)
        t.start()
        tracing["thread"] = t

    if plan["mode"] == "open":
        before = _call(handle, "snapshot")
        t_open = _now()
        if trace:
            trace_window(t_open)
        client_mod.run_open(client, plan["requests"], t_open, seconds)
        edges = {"t_open": t_open, "t_close": t_open + seconds,
                 "offered": len(plan["requests"]), "drained": False}
        after = _call(handle, "snapshot")
        left = client.join(120.0)
    else:
        marks = {}

        def on_open(t_open):
            marks["before"] = _call(handle, "snapshot")
            if trace:
                trace_window(t_open)

        edges = client_mod.run_closed(client, plan["requests"], plan["in_flight"],
                                      plan["ramp"], seconds, on_open)
        before, after = marks["before"], _call(handle, "snapshot")
        deadline = _now() + 120.0
        while _call(handle, "finish_fast") and _now() < deadline:
            time.sleep(0.05)
        left = client.join(60.0)
    if trace:
        tracing["thread"].join(120.0)
        tracing["trace"] = _call(handle, "reduced_trace")
    engine = _call(handle, "records", edges["t_open"], edges["t_close"])
    return {"requests": client.records, "edges": edges, "engine": engine,
            "counters": {"open": before, "close": after},
            "trace": tracing.get("trace"), "streams_left_open": left}


def serve_numbers(run: dict, seconds: float) -> dict:
    """End-to-end numbers of one serving window, all from client clocks."""
    recs = run["requests"]
    ok = stats.answered(recs)
    edges = run["edges"]
    out = {"out_tokens_per_s": stats.tokens_between(
        recs, edges["t_open"], edges["t_close"]) / seconds}
    timed = [r for r in ok if r["due"] >= edges["t_open"]]
    if timed:
        out["ttft_p90_ms"] = stats.percentile([stats.ttft_ms(r) for r in timed], 90)
        out["ttft_p50_ms"] = stats.percentile([stats.ttft_ms(r) for r in timed], 50)
    gaps = [g for g in (stats.tpot_ms(r) for r in timed) if g is not None]
    if gaps:
        out["tpot_p50_ms"] = stats.percentile(gaps, 50)
    return out


def serve_cell(args, bench, cell, config, traffic, generator, platform) -> dict:
    from ray_tpu import serve

    from perfbench.actors import ServeReplica

    builder = loader.module("builders", config["builder"])
    sizes = builder.model_sizes(config, args.rehearse)
    plan = generator.plan(traffic, args.seed, args.seconds, sizes)
    app = serve.deployment(
        ServeReplica, ray_actor_options={"num_tpus": 1},
        max_ongoing_requests=1024,
    ).bind(config, args.seed, platform, args.rehearse, args.out_dir)
    t_spawn = _now()
    handle = serve.run(app, name="perfbench")
    try:
        facts = _call(handle, "setup_facts")
        facts["actor_ready_s"] = _now() - t_spawn
        warm = _call(handle, "warm", plan["warm"])
        for req in plan["setup"]:
            _call(handle, "generate", req["prompt"], req["max_tokens"])
        say("set-up", {**facts, **warm, "plan_requests": len(plan["requests"])})
        if args.sweep:
            return sweep(args, handle, traffic, generator, sizes)
        run = serve_window(handle, plan, args.seconds, args.trace)
        setup_s = run["edges"]["t_open"] - T_PROCESS
        numbers = serve_numbers(run, args.seconds)
        final = _call(handle, "snapshot")
        # after the window: the engine against the plain reference
        import numpy as np
        rng = np.random.default_rng([args.seed, 99])
        prompts = [rng.integers(0, sizes["vocab"], n).tolist()
                   for n in plan["check_prompt_lens"]]
        check = _call(handle, "check", prompts, 9, args.control_dtype)
        routed = sizes["n_experts"] > 0
        if args.control_dtype:
            check["control"]["fails_as_it_should"] = not stats.logprobs_agree(
                check["control"], routed)
        say("reference check", check)
    finally:
        try:
            _call(handle, "close", timeout_s=60.0)
        finally:
            serve.shutdown()
    recs = run["requests"]
    inside = [r for r in recs if r["done"] is None
              or r["done"] >= run["edges"]["t_open"]]
    failed = sum(1 for r in inside if r["error"] is not None
                 or (plan["mode"] == "open"
                     and len(r["token_times"]) != r["max_tokens"]))
    compiles = (run["counters"]["close"]["compile"]["programs"]
                - run["counters"]["open"]["compile"]["programs"])
    correct = (stats.logprobs_agree(check, routed)
               and compiles == 0 and not run["edges"]["drained"]
               and run["streams_left_open"] == 0)
    say("window", {**numbers, "setup_s": setup_s, "compiles_in_window": compiles,
                   "offered": run["edges"]["offered"],
                   "tokens_by_second": stats.tokens_by_second(
                       recs, run["edges"]["t_open"], args.seconds),
                   "streams_left_open": run["streams_left_open"],
                   "failed": failed, "errors": sorted(
                       {r["error"] for r in recs if r["error"]})[:3]})
    run.update(device=facts["device"], setup=dict(facts, **warm),
               memory_peak_bytes=final["memory_peak_bytes"], sizes=sizes,
               peaks=_peaks(args, facts["device"]))
    wanted = [m["name"] for m in loader.metrics_of(bench, "end_to_end", cell["name"])]
    end_to_end = {k: dict(numbers, setup_s=setup_s)[k] for k in wanted}
    return result_line(bench, cell["name"], args.trace, run, end_to_end,
                       correct, len(inside), failed)


def sweep(args, handle, traffic, generator, sizes) -> dict:
    """The knee sweep: one window a rate, on one warmed replica. The knee is
    the highest rate at which the backlog (requests sent and not finished)
    does not grow over the window; its table goes into PERF.md and four
    fifths of it into the mix's file, by hand."""
    rows = []
    for rate in [float(r) for r in args.sweep.split(",")]:
        plan = generator.plan(dict(traffic, rate_rps=rate), args.seed,
                              args.seconds, sizes)
        run = serve_window(handle, plan, args.seconds, False)
        recs, edges = run["requests"], run["edges"]

        def backlog(t):
            return sum(1 for r in recs if r["sent"] <= t
                       and (r["done"] is None or r["done"] > t))
        syncs = run["engine"]["syncs"]
        waits = [w[1] * 1e3 for w in run["engine"]["slot_waits"]] or [0]
        row = {"rate_rps": rate, "requests": len(recs),
               **serve_numbers(run, args.seconds),
               "backlog_at_half": backlog(edges["t_open"] + 0.5 * args.seconds),
               "backlog_at_end": backlog(edges["t_close"]),
               "drain_s": max(r["done"] or edges["t_close"] for r in recs)
               - edges["t_close"],
               "occupancy_mean": (sum(s[1] for s in syncs) / max(len(syncs), 1)),
               "slot_wait_p50_ms": stats.percentile(waits, 50),
               "slot_wait_p90_ms": stats.percentile(waits, 90)}
        rows.append(row)
        say("sweep", row)
    return {"sweep": rows}


# --------------------------------------------------------------------------
# training cells
# --------------------------------------------------------------------------

def train_cell(args, bench, cell, config, traffic, generator, platform) -> dict:
    from ray_tpu import train

    from perfbench.actors import train_loop

    group = config["rehearsal"] if args.rehearse else config
    plan = generator.plan(traffic, args.seed, args.seconds,
                          {"seq_len": group["train"]["seq_len"]})
    t_spawn = _now()
    result = train.JaxTrainer(
        train_loop,
        train_loop_config={
            "config": config, "plan": plan, "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace, "chips": cell["chips"],
            "platform": platform, "rehearse": args.rehearse,
            "out_dir": args.out_dir},
        scaling_config=train.ScalingConfig(use_tpu=True,
                                           chips_per_worker=cell["chips"]),
        run_config=train.RunConfig(name="perfbench",
                                   storage_path=os.path.join(args.out_dir, "train")),
    ).fit()
    if result.error is not None:
        raise RuntimeError("the train loop failed in its worker") from result.error
    got = result.metrics
    steps = len(got["step_ends"])
    elapsed = got["step_ends"][-1] - got["t0"]
    tokens_per_s = steps * got["tokens_per_step"] / elapsed
    setup_s = got["t0"] - T_PROCESS      # one clock base on one machine
    losses = got["losses"]
    loss_err = abs(losses[0] - got["reference_loss"])
    correct = (all(map(math.isfinite, losses)) and loss_err <= LOSS_TOL
               and got["compiles_in_window"] == 0)
    say("train", {"steps": steps, "elapsed_s": elapsed, "first_loss": losses[0],
                  "reference_loss": got["reference_loss"], "loss_err": loss_err,
                  "last_loss": losses[-1], "compile": got["compile"],
                  "compiles_in_window": got["compiles_in_window"],
                  "setup_s": setup_s})
    run = {"device": got["device"], "memory_peak_bytes": got["memory_peak_bytes"],
           "trace": got["trace"], "train": got, "sizes": got["sizes"],
           "setup": {"compile": got["compile"],
                     "actor_ready_s": got["setup_done"] - t_spawn,
                     "backend_up_s": got["backend_up_s"],
                     "weights_placed_s": got["weights_placed_s"]},
           "peaks": _peaks(args, got["device"]), "chips": cell["chips"]}
    end_to_end = {"train_tokens_per_s_per_chip": tokens_per_s / cell["chips"],
                  "setup_s": setup_s}
    return result_line(bench, cell["name"], args.trace, run, end_to_end,
                       correct, steps, 0)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def run_cell(args, bench) -> dict:
    import ray_tpu
    cell = loader.cell(bench, args.workload)
    config = loader.config_of(bench, cell["config"])
    traffic = loader.traffic_of(cell["traffic"])
    if args.rehearse:
        traffic = {**traffic, **traffic["rehearsal"]}
    generator = loader.module("generators", traffic["generator"])
    platform = "cpu" if args.rehearse else "tpu"
    ray_tpu.init()
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < cell["chips"]:
            raise SystemExit(
                f"{cell['name']} needs {cell['chips']} TPU chip(s), this host "
                f"shows {have} (device nodes under /dev/accel* or /dev/vfio/)")
        kind = serve_cell if generator.KIND == "serve" else train_cell
        line = kind(args, bench, cell, config, traffic, generator, platform)
    finally:
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise RuntimeError("the benchmark's parent imported jax; on libtpu "
                           "that takes the chip from the workers")
    return line


def rehearse(args, bench) -> int:
    """Every cell's control flow end to end on the CPU at `tiny` sizes. The
    numbers are printed under `rehearsal.*` names only: a CPU timing is never
    a device metric, and `correct` here says nothing about a device."""
    names = [args.workload] if args.workload else [
        w["name"] for w in bench["workloads"]]
    for name in names:
        chips = loader.cell(bench, name)["chips"]
        os.environ.update(
            JAX_PLATFORMS="cpu", RAY_TPU_NUM_CHIPS=str(chips),
            XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
        args.workload = name
        for trace in (0, 1):
            args.trace = trace
            line = run_cell(args, bench)
            print(json.dumps({
                "rehearsal.workload": name, "rehearsal.trace": trace,
                "rehearsal.agrees_with_reference_on_cpu": line["correct"],
                "rehearsal.attempted": line["attempted"],
                "rehearsal.failed": line["failed"],
                "rehearsal.device": line["device"]["platform"],
                **{"rehearsal." + k: v["value"]
                   for k, v in line["metrics"].items()}}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", help="comma-separated base rates (requests/s)")
    ap.add_argument("--print-per-layer", action="store_true")
    ap.add_argument("--control-dtype", help="also compare the reference with "
                    "its weights cast to this type (float8_e4m3fn) against "
                    "itself: what the tolerances of `correct` make of a lower "
                    "precision (logged, not judged)")
    args = ap.parse_args()
    bench = loader.benchmark()
    if args.print_per_layer:
        print(json.dumps(loader.per_layer_entries(bench), indent=2))
        return 0
    args.out_dir = os.path.join(ROOT, "perfbench_out")
    os.makedirs(args.out_dir, exist_ok=True)
    # programs that compile in under a second are cached too: every program
    # of a warm run comes from the cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(DEADLINE_S)
    if args.rehearse:
        args.seconds = args.seconds or 6.0
        return rehearse(args, bench)
    if not args.workload:
        ap.error("--workload is required")
    args.seconds = args.seconds or float(bench["run_seconds"])
    line = run_cell(args, bench)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
