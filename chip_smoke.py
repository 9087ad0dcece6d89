"""chip_smoke.py — the standing proof that ray_tpu starts on a TPU chip.

    python chip_smoke.py            # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4  # one 4-chip host: fsdp train + 4 one-chip actors

It drives the two normal entry points through `import ray_tpu` only, from a
driver process that never imports jax (a libtpu chip belongs to the one
process that opened it):

- train: `JaxTrainer(loop, ScalingConfig(use_tpu=True, chips_per_worker=N))
  .fit()` — the llama_1b step bench.py measures (bf16 params, batch 4 x seq
  2048, flash attention, no remat, chunked cross entropy, adamw, donated), a
  few steps on one fixed batch inside the chip-bound TrainWorker; the loss
  must be finite and fall;
- serve: `serve.run(...)` of a `num_tpus=1` deployment that owns a paged,
  prefix-caching llama_1b `LLMServer`; eight requests through the handle, half
  of them sharing a 128-token prefix, one streamed. It starts after the train
  actor was killed, in the same session, so "a killed chip-bound actor frees
  its chip" is part of what passes.

Inside each chip-bound actor it asserts the platform, checks the pallas
kernels against their references at llama_1b shapes, and reads the compiled
train and decode programs for the Mosaic custom calls. Any failed phase raises;
nothing is downgraded to a warning. The last line of stdout is one JSON object
`{"ok": true, "device": {...}}` with the device as jax reports it from inside
the chip-bound actor. Without a chip it exits non-zero and prints no result.

Times printed here are phase bookkeeping (compile vs run seconds), not a
benchmark: the repo's numbers come from bench.py.
"""

import argparse
import dataclasses
import json
import math
import re
import signal
import sys
import time

import ray_tpu
from ray_tpu import serve, train
from ray_tpu.serve.llm import LLMConfig, LLMServer

_MOSAIC = "tpu_custom_call"
_TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
_DECODE_KERNELS = ("paged_decode",)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. The defaults are the chip run; a tier-1 test
    rehearses the same code on the CPU with `tiny` sizes, pallas interpret
    mode and `platform="cpu"` so the script cannot rot between chip runs. Run
    as a script there is only the chip run."""
    preset: str = "llama_1b"
    platform: str = "tpu"        # what every chip-bound actor must see
    interpret: bool = False      # pallas interpret mode (no Mosaic to find)
    batch: int = 4               # one chip; the 4-chip mesh takes 2 x this
    seq: int = 2048
    steps: int = 6
    # flash check: B, T, H, Kh, D — paged check: Kh, G, D, page
    flash_shape: tuple = (1, 2048, 32, 8, 64)
    paged_shape: tuple = (8, 4, 64, 64)
    slots: int = 8
    max_seq_len: int = 1024
    prompt_len: int = 200
    shared_prefix: int = 128
    max_tokens: int = 32
    requests: int = 8
    actor_timeout_s: float = 900.0


CHIP = Sizes()


# --------------------------------------------------------------------------
# Code that runs INSIDE chip-bound actors (the only places jax is imported).
# --------------------------------------------------------------------------

def _device(sizes: Sizes, chips: int) -> dict:
    """The device as jax reports it in this process; raises unless it is the
    platform the scheduler bound this worker to, at the bound width. A worker
    that fell to the CPU (e.g. JAX_PLATFORMS=cpu leaked from the driver's
    environment) fails here, loudly."""
    import os

    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != sizes.platform or (
            sizes.platform == "tpu" and info["count"] != chips):
        raise RuntimeError(
            f"chip-bound worker sees {info}, expected {chips} x "
            f"{sizes.platform} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
            f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r})")
    return info


class _CompileMeter:
    """Seconds this process spent getting programs ready — tracing and
    lowering (never cached) and the backend compile (or its fetch from the
    persistent cache) — and how often the cache hit: jax's own monitoring
    events, so compile time is separated from run time wherever it happens."""

    def __init__(self):
        import jax
        self.seconds = self.backend_seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("/backend_compile_duration"):
                self.backend_seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self) -> dict:
        import jax
        return {"compile_s": round(self.seconds, 2),
                "backend_compile_s": round(self.backend_seconds, 2),
                "cache_hits": self.hits, "cache_misses": self.misses,
                "cache_dir": jax.config.jax_compilation_cache_dir}


def _mosaic_calls(hlo: str, kernels, sizes: Sizes) -> dict:
    """How often each named pallas kernel appears as a Mosaic custom call in
    a compiled program's text; raises when one is missing on the chip."""
    lines = [ln for ln in hlo.splitlines() if _MOSAIC in ln]
    found = {k: sum(1 for ln in lines if k in ln) for k in kernels}
    if not sizes.interpret and not all(found.values()):
        raise RuntimeError(
            f"compiled program lacks Mosaic custom calls: {found} "
            f"({len(lines)} {_MOSAIC} lines; first: {lines[:1]!r:.900})")
    return found


def _flash_kernel_check(sizes: Sizes) -> dict:
    """flash fwd + grads vs mha_reference, bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.flash_attention import flash_attention

    b, t, h, kh, d = sizes.flash_shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, do = (jax.random.normal(k, (b, t, h, d), jnp.bfloat16)
             for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(k, (b, t, kh, d), jnp.bfloat16)
            for k in (ks[1], ks[2]))

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *grads)]

    got = run(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                              interpret=sizes.interpret))
    want = run(lambda q, k, v: mha_reference(q, k, v, causal=True))
    # bf16 has 8 bits of mantissa: errors are judged against the largest
    # reference value of each tensor
    errs = {}
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        errs[name] = float(np.max(np.abs(a - w)) / np.max(np.abs(w)))
        if not np.isfinite(a).all() or errs[name] > 2e-2:
            raise RuntimeError(f"flash {name} off mha_reference: rel err "
                               f"{errs[name]:.4f} at {sizes.flash_shape}")
    return {k: round(v, 5) for k, v in errs.items()}


def _paged_kernel_check(sizes: Sizes) -> dict:
    """paged decode vs paged_attention_reference over fragmented pages, bf16,
    lengths from 1 token to a full table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    kh, g, d, page = sizes.paged_shape
    max_pages = sizes.max_seq_len // page
    lengths = np.linspace(1, max_pages * page, sizes.slots).astype(np.int32)
    rng = np.random.default_rng(0)
    pool = sizes.slots * max_pages + 1
    layers = 2                       # a stacked pool; the kernel reads the last
    kp, vp = (jnp.asarray(rng.normal(size=(layers, kh, pool, page, d)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(sizes.slots, kh * g, d)), jnp.bfloat16)
    perm = rng.permutation(np.arange(1, pool))      # scrambled page order
    tables = np.zeros((sizes.slots, max_pages), np.int32)
    used = 0
    for i, n in enumerate(-(-lengths // page)):
        tables[i, :n] = perm[used:used + n]
        used += n
    args = (q, kp, vp, layers - 1, jnp.asarray(tables), jnp.asarray(lengths))
    got = jax.jit(lambda *a: paged_attention(*a, interpret=sizes.interpret))(*args)
    want = paged_attention_reference(*args)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    if not bool(jnp.isfinite(got.astype(jnp.float32)).all()) or err > 2e-2:
        raise RuntimeError(f"paged decode off its reference: max abs err "
                           f"{err:.4f} at {sizes.paged_shape}")
    return {"max_abs_err": round(err, 5)}


def _shard_facts(params, tokens, hlo: str, sizes: Sizes, chips: int) -> dict:
    """Multi-chip train: every device holds parameter shards and batch rows,
    device memory is of the same order everywhere, and the flash custom call
    sees the per-device batch, not an all-gathered one."""
    import jax

    wq = params["params"]["layers_0"]["attn"]["wq"]["kernel"]
    param_devs = sorted(s.device.id for s in wq.addressable_shards)
    row_devs = sorted(s.device.id for s in tokens.addressable_shards)
    rows = {s.data.shape[0] for s in tokens.addressable_shards}
    facts = {"param_shard_devices": param_devs, "batch_row_devices": row_devs,
             "rows_per_device": sorted(rows),
             "wq_shard_shape": list(wq.addressable_shards[0].data.shape)}
    if len(set(param_devs)) != chips or len(set(row_devs)) != chips:
        raise RuntimeError(f"not every chip holds params and batch: {facts}")
    if sizes.platform == "tpu":
        used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        facts["bytes_in_use"] = used
        if max(used) > 4 * min(used):
            raise RuntimeError(f"device memory is lopsided: {used}")
    if not sizes.interpret:
        # kernel-layout operands are [B, H, T, D]: B must be the local batch
        pat = re.compile(r"bf16\[(\d+),\d+,%d,\d+\]" % sizes.seq)
        seen = {int(b) for ln in hlo.splitlines() if _MOSAIC in ln
                and "flash_" in ln for b in pat.findall(ln)}
        facts["flash_call_batch"] = sorted(seen)
        if seen != rows:
            raise RuntimeError(
                f"flash custom call sees batch {sorted(seen)}, expected the "
                f"per-device batch {sorted(rows)} of {tokens.shape[0]}")
    return facts


def train_loop(config):
    """The JaxTrainer loop: runs in the chip-bound TrainWorker actor."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig, llama_param_count
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.sharding import data_sharding
    from ray_tpu.train.lm import make_lm_train_step

    sizes, chips = Sizes(**config["sizes"]), config["chips"]
    n_batch = sizes.batch if chips == 1 else 2 * sizes.batch
    meter = _CompileMeter()
    facts = {"device": _device(sizes, chips),
             "flash_vs_reference": _flash_kernel_check(sizes)}
    kernel_compile_s = meter.seconds

    cfg = getattr(LlamaConfig, sizes.preset)(
        max_seq_len=sizes.seq, param_dtype=jnp.bfloat16, remat=False,
        attn_impl="flash")
    facts["model"] = {"preset": sizes.preset, "n_layers": cfg.n_layers,
                      "d_model": cfg.d_model,
                      "params_m": round(llama_param_count(cfg) / 1e6),
                      "batch": n_batch, "seq": sizes.seq}
    mesh = make_mesh({"fsdp": chips}) if chips > 1 else None
    params, opt_state, step = make_lm_train_step(
        cfg, optax.adamw(1e-4), jax.random.PRNGKey(0), mesh=mesh)
    batch = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (n_batch, sizes.seq + 1), dtype=np.int32)
    tokens = jax.device_put(batch, data_sharding(mesh) if mesh else None)

    with jax.set_mesh(mesh) if mesh else contextlib.nullcontext():
        compiled = step.lower(params, opt_state, tokens).compile()
        hlo = compiled.as_text()
        facts["mosaic_calls"] = _mosaic_calls(hlo, _TRAIN_KERNELS, sizes)
        if mesh is not None:
            facts["sharding"] = _shard_facts(params, tokens, hlo, sizes, chips)
        for i in range(sizes.steps):
            t0 = time.perf_counter()
            params, opt_state, loss = compiled(params, opt_state, tokens)
            loss.block_until_ready()
            # the loss goes out as the device scalar it is: what reaches the
            # driver must be a host value (serialization's job, checked there)
            train.report({"step": i, "loss": loss,
                          "step_s": time.perf_counter() - t0})
    facts["compile"] = {**meter.read(),
                        "kernel_check_compile_s": round(kernel_compile_s, 2)}
    stats = jax.devices()[0].memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    train.report({"step": sizes.steps, "loss": loss, "facts": facts})


class SmokeLLM:
    """The serve deployment: owns an LLMServer on the chip it was bound to."""

    def __init__(self, sizes: dict):
        self.sizes = Sizes(**sizes)
        self.meter = _CompileMeter()
        self.device = _device(self.sizes, 1)
        t0 = time.perf_counter()
        self.server = LLMServer(LLMConfig(
            preset=self.sizes.preset, paged=True, prefix_cache=True,
            max_batch_slots=self.sizes.slots,
            max_seq_len=self.sizes.max_seq_len))
        self.build_s = time.perf_counter() - t0

    async def generate(self, prompt_ids, max_tokens):
        return await self.server.generate(prompt_ids, max_tokens=max_tokens)

    async def generate_stream(self, prompt_ids, max_tokens):
        async for tok in self.server.generate_stream(prompt_ids,
                                                     max_tokens=max_tokens):
            yield tok

    def facts(self) -> dict:
        """Called after the requests: kernel check, compiled decode program,
        engine counters."""
        hlo = self.server.lower_decode_chunk().compile().as_text()
        return {"device": self.device,
                "paged_vs_reference": _paged_kernel_check(self.sizes),
                "mosaic_calls": _mosaic_calls(hlo, _DECODE_KERNELS, self.sizes),
                "server_build_s": round(self.build_s, 2),
                "compile": self.meter.read(),
                "stats": self.server.stats()}


class OneChip:
    """One of four actors alive at once, each bound to one chip of the host."""

    def __init__(self, sizes: dict):
        self.device = _device(Sizes(**sizes), 1)

    def matmul(self) -> dict:
        import os

        import jax
        import jax.numpy as jnp
        x = jnp.ones((1024, 1024), jnp.bfloat16)
        y = jax.jit(lambda a: (a @ a).astype(jnp.float32))(x)
        opened = set()  # the chip device node(s) this process has open
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue
            if re.fullmatch(r"/dev/(vfio/\d+|accel\d+)", target):
                opened.add(target)
        return {"device": self.device, "result": float(y[0, 0]),
                "tpu_ids": ray_tpu.get_tpu_ids(), "opened": sorted(opened),
                "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
                "pid": os.getpid()}


# --------------------------------------------------------------------------
# Driver-side phases. No jax here.
# --------------------------------------------------------------------------

def _say(phase: str, facts: dict):
    print(f"[chip_smoke] {phase}: {json.dumps(facts, default=str)}", flush=True)


def train_phase(sizes: Sizes, chips: int) -> dict:
    t0 = time.perf_counter()
    result = train.JaxTrainer(
        train_loop,
        train_loop_config={"sizes": dataclasses.asdict(sizes), "chips": chips},
        scaling_config=train.ScalingConfig(use_tpu=True,
                                           chips_per_worker=chips),
        run_config=train.RunConfig(name="chip_smoke"),
    ).fit()
    if result.error is not None:
        raise RuntimeError("train phase failed in its worker") from result.error
    hist = [m for m in result.metrics_history if "step_s" in m]
    losses = [float(m["loss"]) for m in hist]
    if len(losses) != sizes.steps or not all(map(math.isfinite, losses)) \
            or not losses[-1] < losses[0]:
        raise RuntimeError(f"loss must be finite and fall: {losses}")
    facts = dict(result.metrics["facts"])
    facts.update(losses=[round(x, 4) for x in losses],
                 step_s=[round(m["step_s"], 3) for m in hist],
                 phase_s=round(time.perf_counter() - t0, 1))
    _say(f"train x{chips}", facts)
    return facts


def serve_phase(sizes: Sizes) -> dict:
    import random
    t0 = time.perf_counter()
    app = serve.deployment(
        SmokeLLM, ray_actor_options={"num_tpus": 1},
        max_ongoing_requests=2 * sizes.requests,
    ).bind(dataclasses.asdict(sizes))
    handle = serve.run(app, name="chip_smoke")
    try:
        rng = random.Random(0)
        vocab = 256  # ids every preset's vocabulary holds
        shared = [rng.randrange(vocab) for _ in range(sizes.shared_prefix)]
        prompts = []
        for i in range(sizes.requests):
            head = shared if i % 2 == 0 else []
            prompts.append(head + [rng.randrange(vocab) for _ in
                                   range(sizes.prompt_len - len(head))])
        # the first sharer alone, so its prefix pages are published before
        # the others ask for them; it also pays the compiles
        t1 = time.perf_counter()
        first = handle.generate.remote(prompts[0], sizes.max_tokens).result(
            timeout_s=sizes.actor_timeout_s)
        first_s = time.perf_counter() - t1
        t1 = time.perf_counter()
        stream = handle.options(stream=True).generate_stream.remote(
            prompts[1], sizes.max_tokens)
        pending = [handle.generate.remote(p, sizes.max_tokens)
                   for p in prompts[2:]]
        outs = [first["tokens"], list(stream)] + [
            r.result(timeout_s=sizes.actor_timeout_s)["tokens"]
            for r in pending]
        rest_s = time.perf_counter() - t1
        for i, toks in enumerate(outs):
            if len(toks) != sizes.max_tokens or not all(
                    isinstance(t, int) for t in toks):
                raise RuntimeError(f"request {i} returned {toks!r}, expected "
                                   f"{sizes.max_tokens} token ids")
        facts = handle.facts.remote().result(timeout_s=sizes.actor_timeout_s)
        stats = facts.pop("stats")
        decode = stats["decode"]
        facts.update(
            answered=f"{len(outs)}/{sizes.requests}",
            decode_syncs=decode["host_syncs"], decode_tokens=decode["tokens"],
            chunk_sizes=decode["chunk_sizes"],
            prefix_hit_tokens=stats["prefix_hit_tokens"],
            first_request_s=round(first_s, 2),
            other_requests_s=round(rest_s, 2),
            phase_s=round(time.perf_counter() - t0, 1))
        if not decode["host_syncs"] or not stats["prefix_hit_tokens"]:
            raise RuntimeError(f"no decode syncs or no prefix hits: {facts}")
        _say("serve", facts)
        return facts
    finally:
        serve.shutdown()


def four_actor_phase(sizes: Sizes, chips: int) -> dict:
    """`chips` one-chip actors alive at once: each sees exactly one device,
    all on distinct chips, each completes a jitted matmul."""
    Actor = ray_tpu.remote(num_tpus=1)(OneChip)
    actors = [Actor.remote(dataclasses.asdict(sizes)) for _ in range(chips)]
    try:
        got = ray_tpu.get([a.matmul.remote() for a in actors],
                          timeout=sizes.actor_timeout_s)
    finally:
        for a in actors:
            ray_tpu.kill(a)
    ids = [tuple(g["tpu_ids"]) for g in got]
    opened = [tuple(g["opened"]) for g in got]
    facts = {"actors": got}
    if (len(set(ids)) != chips or any(g["result"] != 1024.0 for g in got)
            or (sizes.platform == "tpu" and (
                len(set(opened)) != chips or any(len(o) != 1 for o in opened)))):
        raise RuntimeError(f"one-chip actors are not on distinct chips: {got}")
    _say(f"{chips} one-chip actors", facts)
    return facts


def run_phases(sizes: Sizes = CHIP, chips: int = 1) -> dict:
    """Every phase, in one ray_tpu session; returns the device the chip-bound
    train worker saw. Raises on the first failed phase."""
    from ray_tpu._native import build_report
    print(f"[chip_smoke] native control plane: {build_report()}", flush=True)
    ray_tpu.init()
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < chips:
            raise RuntimeError(
                f"need {chips} TPU chip(s), this host shows {have} "
                f"(device nodes under /dev/accel* or /dev/vfio/)")
        device = train_phase(sizes, chips)["device"]
        if chips == 1:
            serve_phase(sizes)
        else:
            four_actor_phase(sizes, chips)
    finally:
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise RuntimeError("the driver imported jax; on libtpu that takes "
                           "the chip from the workers")
    return device


def _deadline(_signum, _frame):
    raise TimeoutError("chip_smoke exceeded its 1100 s budget")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    # a hung chip-bound worker must end as a failure with every process
    # stopped (run_phases' finally), not as the caller's kill at 1200 s
    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(1100)
    device = run_phases(CHIP, args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
