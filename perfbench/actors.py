"""Code that runs INSIDE the chip-bound actors: the serve deployment that owns
an `LLMServer`, and the `JaxTrainer` loop. The only files of the benchmark that
import jax are this one, the builders, `reference.py` and `trace_reduce.py`,
and all of them run here; the parent (`run.py`) never does.

Spans and counters are taken from here, round the calls into each layer:
timestamps on `time.monotonic()` (one clock base with the client on the same
machine), the engine's own exact counters (`LLMServer.stats()`), jax's compile
events, and the profiler's trace, reduced in this process.
"""

import importlib
import os
import shutil
import time

_now = time.monotonic


def load_builder(config: dict):
    return importlib.import_module(
        "perfbench.builders." + config["builder"].removesuffix(".py"))


def device_info(platform: str, chips: int) -> dict:
    """The device as jax reports it here; raises unless it is the platform and
    the number of chips the cell asks for (a worker that fell to the CPU fails
    here, loudly)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != platform or (platform == "tpu"
                                        and info["count"] != chips):
        raise RuntimeError(
            f"chip-bound worker sees {info}, the cell needs {chips} x {platform} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend does not
    report it, as the CPU)."""
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


class CompileMeter:
    """Seconds this process spent getting programs ready (tracing, lowering,
    backend compile or its fetch from the persistent cache) and how often the
    cache hit: jax's own monitoring events (copied from chip_smoke.py)."""

    def __init__(self):
        import jax
        self.seconds = self.backend_seconds = 0.0
        self.hits = self.misses = self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("/backend_compile_duration"):
                self.backend_seconds += duration
                self.programs += 1   # compiled or fetched: one per program

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self) -> dict:
        return {"compile_s": self.seconds,
                "backend_compile_s": self.backend_seconds,
                "programs": self.programs,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Tracer:
    """The jax profiler in the process that holds the chip. `stop()` reduces
    the trace here (the parent has no jax to read it with) and removes it."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        self.t0 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # TraceMe host events only: a python
        opts.host_tracer_level = 2     # frame per call would swamp the trace
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = _now()

    def stop(self):
        """Ends the trace; reading it waits for `reduce()`, after the window."""
        import jax
        jax.profiler.stop_trace()
        self.t1 = _now()

    def reduce(self) -> dict:
        from perfbench import trace_reduce
        reduced = trace_reduce.reduce_file(trace_reduce.find_xplane(self.dir))
        reduced["t0"], reduced["t1"] = self.t0, self.t1
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

class ServeReplica:
    """The deployment: owns the `LLMServer` on the chip it was bound to and
    records, per request and per engine tick, what the per-layer readers
    need."""

    def __init__(self, config: dict, seed: int, platform: str, rehearse: bool,
                 out_dir: str):
        t0 = _now()
        self.meter = CompileMeter()
        self.device = device_info(platform, 1)
        self.backend_up_s = _now() - t0
        builder = load_builder(config)
        self.sizes = builder.model_sizes(config, rehearse)
        self.server = builder.build_server(config, seed, rehearse)
        self.weights_placed_s = _now() - t0
        self.tracer = Tracer(out_dir)
        self.first_token = {}     # request id -> (t_in, t_first)
        self.slot_waits = []      # (t, seconds waited in _reserve)
        self.syncs = []   # (t, active slots, tokens, chunk, seconds, context)
        self.prefill_chunks = []  # (t, tokens in the chunk)
        self._wrap_engine()

    def _wrap_engine(self):
        """Timestamps round the engine's own calls, from outside it."""
        srv = self.server
        reserve, note_sync, prefill = (srv._reserve, srv._note_sync,
                                       srv._prefill_chunk)

        async def timed_reserve(*a, **k):
            t = _now()
            out = await reserve(*a, **k)
            self.slot_waits.append((t, _now() - t))
            return out

        def sampled_sync(tokens, dt_s, chunk=None):
            context = sum(s.prompt_len + len(s.generated)
                          for s in srv._active.values())
            self.syncs.append((_now(), len(srv._active), tokens, chunk, dt_s,
                               context))
            return note_sync(tokens, dt_s, chunk)

        def counted_prefill(job):
            before = job.pos
            out = prefill(job)
            self.prefill_chunks.append((_now(), job.pos - before))
            return out

        srv._reserve, srv._note_sync, srv._prefill_chunk = (
            timed_reserve, sampled_sync, counted_prefill)

    # -- requests ------------------------------------------------------------
    async def generate_stream(self, rid: int, prompt, max_tokens: int):
        t_in, first = _now(), True
        async for tok in self.server.generate_stream(prompt,
                                                     max_tokens=max_tokens):
            if first:
                self.first_token[rid], first = (t_in, _now()), False
            yield tok

    async def generate(self, prompt, max_tokens: int, logprobs: bool = False):
        return await self.server.generate(prompt, max_tokens=max_tokens,
                                          logprobs=logprobs)

    def _warm_shapes(self, want: dict) -> list:
        """(prompt length, max tokens) of one request per program the cell's
        traffic can reach: a fresh row's first chunk at every bucket between
        the shortest and the longest first chunk (chunk-local attention); the
        continuation path (later chunks and prefix hits: the row's whole page
        capacity gathered) at every bucket, where prompts outgrow a chunk or
        share prefixes; and the decode chunks 1, 2, .., `decode_chunk`. The
        buckets are the engine's own (`LLMServer._bucket`)."""
        cfg, bucket = self.server.config, self.server._bucket
        chunk = cfg.prefill_chunk
        lo = bucket(min(want["prompt_min"], chunk))
        hi = bucket(min(want["prompt_max"], chunk))
        sizes = [b for b in (16 << i for i in range(20)) if b <= chunk]
        shapes = [(b, 1) for b in sizes if lo <= b <= hi]
        if want["prompt_max"] > chunk or want["sharing"]:
            shapes += [(chunk + b, 1) for b in sizes]       # final chunks
            shapes += [(2 * chunk + sizes[0], 1)]           # a full middle chunk
        shapes = [(p, n) for p, n in shapes if p + n <= cfg.max_seq_len]
        return shapes + [(sizes[0], 2 * cfg.decode_chunk)]

    async def warm(self, want: dict) -> dict:
        """Run those requests one after another, so that every program the
        window uses is compiled (or fetched) before it opens."""
        import numpy as np
        t0, before = _now(), self.meter.read()
        rng = np.random.default_rng(12345)
        shapes = self._warm_shapes(want)
        for n_prompt, n_out in shapes:
            prompt = rng.integers(0, self.sizes["vocab"], n_prompt).tolist()
            await self.server.generate(prompt, max_tokens=n_out)
        filled = await self._fill_pool(rng)
        after = self.meter.read()
        return {"warm_s": _now() - t0, "warm_requests": len(shapes),
                "pool_fill_requests": filled,
                "warm_compile_s": after["compile_s"] - before["compile_s"],
                "compile": after}

    async def _fill_pool(self, rng) -> int:
        """A replica in service has a pool full of cached prefixes and evicts
        to admit; one that has just started admits into free pages. So that
        the whole window runs in the first regime (and the eviction path's
        programs are ready), fill the pool with throw-away prompts until the
        page manager has had to evict for two of them."""
        cfg, mgr = self.server.config, self.server.page_mgr
        if mgr is None or not cfg.prefix_cache:
            return 0
        n_prompt = min(cfg.max_seq_len - 2, 4 * cfg.prefill_chunk)
        pages = -(-n_prompt // cfg.page_size)
        sent, evicting = 0, 0
        while evicting < 2 and sent < mgr.num_pages // pages + 4:
            evicting += len(mgr.free_pages) < pages + 1
            prompt = rng.integers(0, self.sizes["vocab"], n_prompt).tolist()
            await self.server.generate(prompt, max_tokens=1)
            sent += 1
        return sent

    def close(self):
        """Gives back what the engine keeps outside the process: the shared
        memory segments and spill files of demoted KV pages."""
        stash = getattr(self.server, "_kv_stash", None)
        if stash is not None:
            stash.close()

    def finish_fast(self) -> int:
        """After a saturated window: end every admitted request at the token
        it has reached, so the backlog drains in a tick and not in a minute.
        Returns how many requests are still inside the engine."""
        for slot in self.server._active.values():
            slot.max_tokens = min(slot.max_tokens, len(slot.generated))
        for job in self.server._prefill_q:
            job.slot.max_tokens = 1
        return len(self.server._active) + len(self.server._prefill_q)

    # -- counters, records, trace ---------------------------------------------
    def snapshot(self) -> dict:
        """The engine's exact counters with the time they were read."""
        st = self.server.stats()
        keep = ("active", "free_slots", "requests", "decode", "pages_in_use",
                "pages_free", "prefix_cached_pages", "prefix_hit_tokens",
                "prefix_query_tokens")
        return {"t": _now(), "stats": {k: st[k] for k in keep if k in st},
                "compile": self.meter.read(),
                "memory_peak_bytes": memory_peak_bytes(),
                "slots": self.server.config.max_batch_slots}

    def setup_facts(self) -> dict:
        return {"device": self.device, "backend_up_s": self.backend_up_s,
                "weights_placed_s": self.weights_placed_s,
                "compile": self.meter.read()}

    def records(self, t0: float, t1: float) -> dict:
        """What the wrappers recorded inside [t0, t1]."""
        def inside(rows):
            return [r for r in rows if t0 <= r[0] <= t1]
        return {"first_token": self.first_token,
                "slot_waits": inside(self.slot_waits),
                "syncs": inside(self.syncs),
                "prefill_chunks": inside(self.prefill_chunks)}

    def start_trace(self):
        self.tracer.start()

    def stop_trace(self):
        self.tracer.stop()

    def reduced_trace(self) -> dict:
        return self.tracer.reduce()

    # -- correctness -----------------------------------------------------------
    async def check(self, prompts, max_tokens: int, control_dtype=None) -> dict:
        """Outside the window: the engine's log-probabilities of the tokens it
        generates (prefill, then decode through the paged cache, asked for by
        the normal `generate` call) against the plain reference,
        teacher-forced on the same tokens. With `control_dtype`, also the
        reference with its weights cast to that type against itself."""
        import numpy as np

        from perfbench import reference
        errs, control, finite = [], [], True
        for prompt in prompts:
            out = await self.server.generate(prompt, max_tokens=max_tokens,
                                             logprobs=True)
            toks, got = out["tokens"], np.asarray(out["logprobs"], np.float64)
            sequence = list(prompt) + toks
            want = np.asarray(reference.logprobs_of(
                self.server.params, sequence, self.sizes, len(toks)), np.float64)
            errs += np.abs(got - want).tolist()
            finite = finite and bool(np.isfinite(got).all())
            if control_dtype:
                low = reference.logprobs_of(self.server.params, sequence,
                                            self.sizes, len(toks),
                                            weights_as=control_dtype)
                control += np.abs(np.asarray(low, np.float64) - want).tolist()
        check = {"abs_logprob_errs": [round(e, 4) for e in errs],
                 "finite": finite,
                 "median_abs_logprob_err": float(np.median(errs)),
                 "max_abs_logprob_err": float(np.max(errs)),
                 "prompt_lens": [len(p) for p in prompts]}
        if control_dtype:
            check["control"] = {
                "dtype": control_dtype, "finite": True,
                "abs_logprob_errs": [round(e, 4) for e in control],
                "median_abs_logprob_err": float(np.median(control))}
        return check


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def train_loop(cfg: dict):
    """The `JaxTrainer` loop of a training cell; runs in the chip-bound
    TrainWorker. Steps after warm-up for the whole window with fresh seeded
    host batches made while the device works, every loss read, the first
    step's loss checked against the reference after the window."""
    import contextlib

    import jax
    import numpy as np

    from ray_tpu import train

    t_start = _now()
    meter = CompileMeter()
    config, plan = cfg["config"], cfg["plan"]
    rehearse, chips = cfg["rehearse"], cfg["chips"]
    device = device_info(cfg["platform"], chips)
    backend_up_s = _now() - t_start
    builder = load_builder(config)
    sizes = builder.model_sizes(config, rehearse)
    params, opt_state, step, mesh, place, _ = builder.build_train(
        config, cfg["seed"], chips, rehearse)
    jax.block_until_ready(params)
    weights_placed_s = _now() - t_start
    n_rows, seq = plan["rows_per_chip"] * chips, plan["seq_len"]
    rng = np.random.default_rng(cfg["seed"])

    def next_batch():
        return rng.integers(0, sizes["vocab"], (n_rows, seq + 1), dtype=np.int32)

    first_batch = next_batch()
    tracer = Tracer(cfg["out_dir"])
    scope = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with scope:
        # warm-up: the one step program, compiled or fetched, and run once
        params, opt_state, loss = step(params, opt_state, place(first_batch))
        losses = [float(loss)]
        compile_before = meter.read()
        setup_done = _now()
        step_ends, trace, traced = [], None, bool(cfg["trace"])
        trace_at, trace_steps = plan["trace_after_steps"], plan["trace_steps"]
        batch = place(next_batch())
        t0 = _now()
        while _now() - t0 < cfg["seconds"]:
            i = len(step_ends)
            if traced and i == trace_at:
                tracer.start()
            params, opt_state, loss = step(params, opt_state, batch)
            batch = place(next_batch())      # host work under the device's
            losses.append(float(loss))       # blocks until the step is done
            step_ends.append(_now())
            if traced and i == trace_at + trace_steps - 1:
                tracer.stop()
    compile_after = meter.read()
    peak = memory_peak_bytes()
    if traced:
        trace = dict(tracer.reduce(), steps=trace_steps)

    # after the window: the first step's loss against the plain reference on
    # the same batch and the same initial weights (made again from the seed)
    del params, opt_state, batch
    params0 = builder.build_train(config, cfg["seed"], chips, rehearse)[0]
    from perfbench import reference
    want = float(reference.loss_of(params0, first_batch, sizes))
    train.report({
        "device": device, "losses": losses, "t0": t0, "step_ends": step_ends,
        "t_start": t_start, "setup_done": setup_done,
        "backend_up_s": backend_up_s, "weights_placed_s": weights_placed_s,
        "compile": compile_before,
        "compiles_in_window": (compile_after["programs"]
                               - compile_before["programs"]),
        "memory_peak_bytes": peak, "trace": trace,
        "reference_loss": want,
        "tokens_per_step": n_rows * seq, "rows": n_rows, "seq_len": seq,
        "sizes": sizes})
