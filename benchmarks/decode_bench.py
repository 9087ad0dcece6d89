"""Decode-serving throughput: dense slot KV cache vs paged block tables.

Measures steady-state decode (one token per active slot per step) for the
Llama-1B class on the attached accelerator. Usage:

    python benchmarks/decode_bench.py                # dense + paged @64
    PAGE=128 SKIP_DENSE=1 python benchmarks/decode_bench.py

Timed steps end in `block_until_ready`. Step times on this installation:
not measured.

ROOFLINE, from shapes and the published peak alone: decode is
HBM-bandwidth-bound on reading the weights once per step —

    bytes/step ≈ 2 B/param × 852.6M params (llama_1b bf16)   = 1.71 GB
               + B·L·2·Kh·D·len·2 B of KV   (B=8, len 64:     17 MB)
    v5e HBM 819 GB/s (util/tpu.py peaks table) → step floor ≈ 2.1 ms
    → tok/s ceiling ≈ B / 2.1 ms: B=8 → ~3.8k, B=32 → ~15k, B=64 → ~30k

KV bytes are 1% of weight bytes at these lengths, so the ceiling scales
~linearly in B until KV reads rival weight reads (B·len ≈ 26k tokens at this
config). How close the engine comes to it is ROADMAP S4's question.
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import KVCache, Llama, LlamaConfig
from ray_tpu.ops.paged_attention import PagedKVCache
from ray_tpu.serve.radix_cache import PageManager

B = int(os.environ.get("B", 8))
SMAX = int(os.environ.get("SMAX", 1024))
STEPS = int(os.environ.get("STEPS", 64))
PAGE = int(os.environ.get("PAGE", 64))


def bench_chunked(out):
    """Serving-loop sync amortization (r6 tentpole): drive LLMServer's
    fused multi-token decode and RECORD the amortization — host syncs per
    token, tokens per sync, per-chunk latency — instead of inferring it
    from tok/s. The steady-state window opens once every stream has its
    first token (prefill queue drained → full chunks) and closes at drain.

    Asserts host_syncs_per_token <= 1/N in that window: each sync advances
    every active slot, so B slots leave the bound ~B-fold slack for ragged
    tail chunks. CPU-feasible (tiny preset) so tier-1 boxes can check it:
    CHUNK / CHUNK_TOKENS env-tunable."""
    import asyncio

    from ray_tpu.serve.llm import LLMConfig, LLMServer

    on_tpu = jax.default_backend() not in ("cpu",)
    N = int(os.environ.get("CHUNK", 8))
    mt = int(os.environ.get("CHUNK_TOKENS", 49))
    plen = 16
    prompts = [[(7 * i + j) % 250 + 1 for j in range(plen)]
               for i in range(B)]

    def run(chunk):
        srv = LLMServer(LLMConfig(
            preset="llama_125m" if on_tpu else "tiny",
            max_batch_slots=B, max_seq_len=plen + mt + 16,
            decode_chunk=chunk))

        async def go():
            # warmup: compile prefill buckets + the chunk-length variants
            await asyncio.gather(*[srv.generate(p, max_tokens=mt)
                                   for p in prompts])
            gens = [srv.generate_stream(p, max_tokens=mt) for p in prompts]
            await asyncio.gather(*[g.__anext__() for g in gens])
            s0 = dict(srv.stats()["decode"])
            t0 = time.perf_counter()

            async def drain(g):
                return sum([1 async for _ in g])

            toks_seen = sum(await asyncio.gather(*[drain(g) for g in gens]))
            dt = time.perf_counter() - t0
            s1 = srv.stats()["decode"]
            syncs = s1["host_syncs"] - s0["host_syncs"]
            toks = s1["tokens"] - s0["tokens"]
            # the tick loop decodes ahead into the stream queues while the
            # first tokens are being gathered, so drain sees that backlog
            # on top of the tokens generated inside the [s0, s1] window
            assert toks_seen >= toks, (toks_seen, toks)
            return {"decode_chunk": chunk,
                    "decode_tps": round(toks / dt, 1),
                    "host_syncs": syncs, "tokens": toks,
                    "host_syncs_per_token": round(syncs / max(toks, 1), 5),
                    "tokens_per_sync": round(toks / max(syncs, 1), 2),
                    "chunk_ms_avg": round(
                        (s1["chunk_s_total"] - s0["chunk_s_total"])
                        / max(syncs, 1) * 1e3, 3)}

        return asyncio.run(go())

    chunked = run(N)
    per_step = run(1)
    chunked["speedup_vs_per_step"] = round(
        chunked["decode_tps"] / max(per_step["decode_tps"], 1e-9), 2)
    out["chunked"], out["per_step"] = chunked, per_step
    print(f"chunked(N={N}): {chunked['decode_tps']:,.1f} tok/s, "
          f"{chunked['host_syncs_per_token']} syncs/token "
          f"(bound {1.0 / N:.4f}), {chunked['chunk_ms_avg']} ms/chunk, "
          f"{chunked['speedup_vs_per_step']}x vs per-step")
    # the amortization CLAIM, enforced: steady state must sync at most
    # once per N tokens or this bench FAILS the run
    assert chunked["host_syncs_per_token"] <= 1.0 / N, chunked


def main():
    on_tpu = jax.default_backend() not in ("cpu",)
    # raw step benches use the 1B target on accelerators; CPU boxes get the
    # tiny preset so the bench (and its chunked section below) stays
    # runnable under tier-1 instead of paging through 3.4 GB of f32 params
    cfg = (LlamaConfig.llama_1b(max_seq_len=SMAX, param_dtype=jnp.bfloat16)
           if on_tpu else LlamaConfig.tiny(max_seq_len=SMAX))
    model = Llama(cfg)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()
    tok = jnp.ones((B, 1), jnp.int32)

    def bench(step, cache):
        t0 = time.perf_counter()
        cache, logits = step(params, cache, tok)
        logits.block_until_ready()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(STEPS):
            cache, logits = step(params, cache, tok)
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        return B * STEPS / dt, dt / STEPS * 1e3, compile_s

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(p, cache, t):
        logits, cache = model.apply(p, t, cache=cache)
        return cache, logits

    import json
    out = {"B": B, "smax": SMAX, "page": PAGE, "steps": STEPS}
    if not os.environ.get("SKIP_DENSE"):
        dense = KVCache.init(cfg, B, SMAX).replace(
            length=jnp.full((B,), 64, jnp.int32))
        tps, ms, comp = bench(step, dense)
        print(f"dense: {tps:,.0f} tok/s ({ms:.1f} ms/step, B={B}, "
              f"compile {comp:.1f}s)")
        out.update(dense_tps=round(tps), dense_ms=round(ms, 2),
                   dense_compile_s=round(comp, 1))

    max_pages = SMAX // PAGE
    mgr = PageManager(B * max_pages + 1, PAGE, B, max_pages)
    rows = [mgr.allocate(i, SMAX) for i in range(B)]
    paged = PagedKVCache.init(
        cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, B * max_pages + 1,
        PAGE, B, max_pages, dtype=cfg.dtype).replace(
            block_tables=jnp.asarray(rows, jnp.int32),
            lengths=jnp.full((B,), 64, jnp.int32))
    tps, ms, comp = bench(step, paged)
    print(f"paged: {tps:,.0f} tok/s ({ms:.1f} ms/step, B={B}, page={PAGE}, "
          f"compile {comp:.1f}s)")
    out.update(paged_tps=round(tps), paged_ms=round(ms, 2),
               paged_compile_s=round(comp, 1))
    if not os.environ.get("SKIP_CHUNKED"):
        bench_chunked(out)
    print("JSON:", json.dumps(out))


if __name__ == "__main__":
    main()
