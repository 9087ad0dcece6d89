"""Prefill/Decode disaggregated serving (reference: python/ray/llm/
_internal/serve/serving_patterns/prefill_decode/pd_server.py).

Decode-as-orchestrator, like the reference: the decode server receives
the request, asks a PREFILL server to compute the prompt's KV (the
reference sends a max_tokens=1 request carrying kv_transfer_params and
lets NIXL move the blocks), installs the pages into its own paged
cache, and runs all decode steps locally. Prefill-heavy and
decode-heavy load scale independently — the reference's motivation.

TPU-first re-cut: paged KV pages ARE the transfer unit, and the
hand-off is a STREAMING data plane (kv_transfer.py), not an RPC
payload:

  * prefill seals extracted pages into shm segments per prefill chunk
    and the RPC frames carry only segment metadata — the decode pull of
    chunk i overlaps the prefill compute of chunk i+1;
  * the ship is prefix-aware end to end: prefill reserves with
    use_prefix=True and register_prefix-es completed prompts (hot
    system prompts are computed once per prefill replica), and the
    decode side reserves with use_prefix=True FIRST so only the
    non-cached suffix pages are shipped at all (kv_ship_saved_pages);
  * the decode pull rides, in order of preference: same-host shm
    attach (zero copies end to end), node_agent.parallel_fetch's
    4-stream ranged transfer against the prefill's KVDataServer, or a
    raw-bytes RPC fetch as the last-resort fallback.

Requires paged=True (the dense cache has no page identity to ship).
"""

import asyncio
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.util import metrics as _metrics
from ray_tpu.util import tracing

from . import kv_transfer
from .llm import LLMConfig, LLMServer


def _pages_of(cache, rows) -> list:
    """Pages `rows` of every per-page pool of `cache`, as host arrays in the
    pool's own form (a shipment's bytes)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import gather_pages
    return list(jax.device_get(
        gather_pages(cache, jnp.asarray(rows), page_major=False)))


def _require_paged(server: LLMServer, who: str):
    if server.page_mgr is None:
        raise ValueError(f"{who} needs LLMConfig(paged=True): KV pages are "
                         "the prefill→decode transfer unit")
    if server.model_cfg.n_state_layers:
        raise NotImplementedError(
            f"{who}: the prefill→decode hand-off carries KV pages and not "
            "the recurrent state of linear-attention layers or of a "
            "state-space mixer (LlamaConfig.full_attn_every, ssm_heads); "
            "serve such a model on one colocated LLMServer")
    if server.model_cfg.n_window_layers:
        raise NotImplementedError(
            f"{who}: the prefill→decode hand-off carries the full pool's "
            "pages and not the window pool's of sliding-window layers "
            "(LlamaConfig.layer_types); serve such a model on one "
            "colocated LLMServer")


class _ShipJob:
    """Prefill-side state of one in-flight shipment: the segment list the
    decode side polls via prefill_wait, and the first-token result."""

    __slots__ = ("segments", "done", "token", "logprob", "error", "event",
                 "task")

    def __init__(self):
        self.segments: List[Dict[str, Any]] = []
        self.done = False
        self.token: Optional[int] = None
        self.logprob: Optional[float] = None
        self.error: Optional[BaseException] = None
        self.event = asyncio.Event()
        self.task: Optional[asyncio.Task] = None


class PrefillServer(LLMServer):
    """Prefill-only replica: computes prompt KV + the first token, ships
    both, keeps nothing. Scale this deployment for prompt-heavy load."""

    # ---------------------------------------------------- streaming plane
    def _ship_plane(self) -> kv_transfer.ShipWriter:
        if getattr(self, "_ship_writer", None) is None:
            self._ship_writer = kv_transfer.ShipWriter()
            self._ship_jobs: Dict[str, _ShipJob] = {}
            self._kv_server: Optional[kv_transfer.KVDataServer] = None
            self._kv_loop: Optional[asyncio.AbstractEventLoop] = None
        return self._ship_writer

    async def _ship_data_addr(self) -> Optional[str]:
        import os
        if os.environ.get("RAY_TPU_KV_DATA", "1") == "0":
            return None
        loop = asyncio.get_running_loop()
        if self._kv_server is not None and self._kv_loop is not loop:
            # the listener is bound to a previous (now dead) event loop —
            # remote pulls would connect-refuse and fall back to RPC bytes
            try:
                self._kv_server.close()
            except Exception:  # noqa: BLE001 - dead-loop close best effort
                pass
            self._kv_server = None
        if self._kv_server is None:
            self._kv_server = kv_transfer.KVDataServer(self._ship_writer)
            await self._kv_server.start()
            self._kv_loop = loop
        return self._kv_server.addr

    async def prefill_begin(self, prompt_ids: List[int],
                            skip_pages: int = 0,
                            trace_id: Optional[str] = None,
                            temperature: Optional[float] = None,
                            top_p: Optional[float] = None,
                            top_k: Optional[int] = None,
                            logprobs: bool = False) -> Dict[str, Any]:
        """Reserve a slot and start the chunked prefill + per-chunk
        shipment in the background; returns the ship header immediately
        (segment metadata follows via prefill_wait). `skip_pages` leading
        pages are never shipped — the decode side already holds them in
        its prefix cache (suffix-only delta)."""
        from ray_tpu.ops.paged_attention import page_layout

        _require_paged(self, "PrefillServer")
        self._ship_plane()
        cfg = self.config
        prompt = list(prompt_ids)
        P = len(prompt)
        ps = cfg.page_size
        total_pages = -(-P // ps)
        skip_pages = max(0, min(int(skip_pages), total_pages - 1))
        # prefix-aware reservation: leading pages already in THIS replica's
        # cache are skipped by compute (pos starts at `cached`) — hot
        # system prompts prefill once per replica
        slot_idx, cached = await self._reserve(prompt, P, use_prefix=True)
        ship_id = kv_transfer.new_ship_id()
        job = _ShipJob()
        self._ship_jobs[ship_id] = job
        _metrics.get_or_create(
            _metrics.Counter, "kv_ship_requests",
            "PD requests served via the streaming KV plane").inc()
        if skip_pages:
            _metrics.get_or_create(
                _metrics.Counter, "kv_ship_saved_pages",
                "KV pages NOT shipped: decode already held them in its "
                "prefix cache").inc(skip_pages)
        job.task = asyncio.ensure_future(self._run_ship(
            ship_id, job, slot_idx, prompt, cached, skip_pages, trace_id,
            temperature, top_p, top_k, logprobs))
        return {"ship_id": ship_id,
                "layout": page_layout(self.cache),
                "prompt_len": P, "page_size": ps,
                "skip_pages": skip_pages, "total_pages": total_pages,
                "prefill_cached_tokens": cached,
                "data_addr": await self._ship_data_addr()}

    async def _run_ship(self, ship_id: str, job: _ShipJob, slot_idx: int,
                        prompt: List[int], cached: int, skip_pages: int,
                        trace_id: Optional[str], temperature, top_p, top_k,
                        logprobs: bool):
        import jax
        import jax.numpy as jnp

        from .llm import _PrefillJob

        cfg = self.config
        ps = cfg.page_size
        P = len(prompt)
        total_pages = -(-P // ps)
        writer = self._ship_writer
        published = skip_pages
        seg_index = 0
        try:
            pj = _PrefillJob(slot_idx=slot_idx, slot=None,
                             prompt=np.asarray(prompt, np.int32), pos=cached)
            last_logits = None
            while True:
                # seal every fully-written page compute has passed — the
                # decode pull of these overlaps the next chunk below
                done_pages = (total_pages if last_logits is not None
                              else pj.pos // ps)
                if done_pages > published:
                    with tracing.span("serve.pd.kv_seal", "serve",
                                      trace_id=trace_id,
                                      args={"pages": done_pages - published}):
                        seg = self._publish_pages(
                            writer, ship_id, seg_index, slot_idx,
                            published, done_pages - published)
                    job.segments.append(seg)
                    seg_index += 1
                    published = done_pages
                    job.event.set()
                if last_logits is not None:
                    break
                with tracing.span("serve.pd.prefill_chunk", "serve",
                                  trace_id=trace_id, args={"pos": pj.pos}):
                    last_logits = self._prefill_chunk(pj)
                await asyncio.sleep(0)   # let waiters/pulls interleave
            if cfg.prefix_cache and self.page_mgr is not None:
                # publish this prompt's full pages so the NEXT request
                # sharing the prefix prefills only its suffix here
                self.page_mgr.register_prefix(slot_idx, prompt)
            self._sample_key, sub = jax.random.split(self._sample_key)
            first, flogp = self._sample_first(
                last_logits, sub,
                jnp.float32(cfg.temperature if temperature is None
                            else temperature),
                jnp.float32(cfg.top_p if top_p is None else top_p),
                jnp.int32(cfg.top_k if top_k is None else top_k),
                logprobs)
            job.token = int(first)
            if logprobs:
                job.logprob = float(flogp)
        except BaseException as e:  # noqa: BLE001 - surface via prefill_wait
            job.error = e
        finally:
            job.done = True
            self._release_slot(slot_idx)
            job.event.set()

    def _publish_pages(self, writer: kv_transfer.ShipWriter, ship_id: str,
                       seg_index: int, slot_idx: int, page_start: int,
                       n_pages: int) -> Dict[str, Any]:
        """Extract the slot's pages [page_start, page_start+n_pages) of
        every per-page pool as host arrays ([L,Kh,n,ps,D] k and v in the
        dense layout) and seal them into one shm segment. Whole raw pages
        ship — attention masks by length, so the unfilled tail of the final
        page needs no zero-padding round trip."""
        rows = np.asarray(self.page_mgr.table_slice(
            slot_idx, page_start, n_pages), np.int32)
        return writer.publish(ship_id, seg_index, _pages_of(self.cache, rows),
                              page_start, n_pages)

    async def prefill_wait(self, ship_id: str,
                           have: int = 0) -> Dict[str, Any]:
        """Block until more than `have` segments are published (or the
        prefill finished); returns the new segment metadata — never KV
        bytes."""
        job = self._ship_jobs.get(ship_id)
        if job is None:
            raise KeyError(f"unknown shipment {ship_id}")
        while len(job.segments) <= have and not job.done:
            job.event.clear()
            await job.event.wait()
        if job.error is not None:
            raise RuntimeError("prefill failed") from job.error
        out: Dict[str, Any] = {"segments": job.segments[have:],
                               "done": job.done}
        if job.done:
            out["token"] = job.token
            if job.logprob is not None:
                out["logprob"] = job.logprob
        return out

    async def prefill_fetch(self, ship_id: str, oid: str) -> bytes:
        """Raw segment bytes — the RPC fallback for a decode replica that
        can neither attach the segment nor reach the data server."""
        self._ship_plane()
        return self._ship_writer.read_segment(oid)

    async def prefill_drop(self, ship_id: str) -> bool:
        """Free a shipment's segments (decode finished installing, or the
        request died)."""
        job = self._ship_jobs.pop(ship_id, None) if getattr(
            self, "_ship_jobs", None) else None
        if job is not None and job.task is not None and not job.done:
            job.task.cancel()
        if getattr(self, "_ship_writer", None) is not None:
            self._ship_writer.drop_ship(ship_id)
        return True


class ShipSource:
    """Decode-side endpoint bundle for one prefill replica's shipment API:
    a direct PrefillServer (in-process tests/bench) or a serve
    DeploymentHandle. Only metadata and the RPC-fallback bytes ever cross
    it."""

    def __init__(self, prefill):
        self._p = prefill
        self._direct = isinstance(prefill, PrefillServer)

    async def _call(self, name: str, *a, **kw):
        if self._direct:
            return await getattr(self._p, name)(*a, **kw)
        # serve DeploymentHandle: .remote() does sync controller IO (keep
        # it off the loop); the DeploymentResponse itself is awaitable
        loop = asyncio.get_running_loop()
        resp = await loop.run_in_executor(
            None, lambda: getattr(self._p, name).remote(*a, **kw))
        return await resp

    def begin(self, prompt, skip_pages, trace_id, temperature, top_p,
              top_k, logprobs):
        return self._call("prefill_begin", prompt, skip_pages=skip_pages,
                          trace_id=trace_id, temperature=temperature,
                          top_p=top_p, top_k=top_k, logprobs=logprobs)

    def wait(self, ship_id, have):
        return self._call("prefill_wait", ship_id, have)

    def fetch(self, ship_id, oid):
        return self._call("prefill_fetch", ship_id, oid)

    def drop(self, ship_id):
        return self._call("prefill_drop", ship_id)


class DecodeServer(LLMServer):
    """Decode replica that can admit a request whose prompt KV was computed
    elsewhere: install pages, skip prefill entirely, decode as usual.

    Decode here means the inherited fused multi-token tick (llm.py
    decode_chunk): with no local prefill queue competing, a pure-decode
    replica sits in steady state almost immediately, so PD decode is the
    best case for host-sync amortization — each tick advances every slot
    up to `decode_chunk` tokens with one host round-trip. stats()['decode']
    (tokens_per_sync, chunk latency) reports it per replica."""

    def _pd_slo_tags(self) -> Dict[str, str]:
        return {"engine": self._slo_tags["engine"], "path": "pd"}

    async def _admit_with_kv(self, prompt: List[int], source: ShipSource,
                             max_tokens: int, eos_id, stream: bool,
                             temperature, top_p, top_k, logprobs,
                             t_request: Optional[float] = None):
        """Install the KV `source`'s prefill replica ships into a reserved
        slot and hand the request to the decode tick loop; returns
        (slot_idx, slot, finished_early). Reserves prefix-aware, asks
        prefill for the non-cached suffix only, installs segments as they
        seal (pull of chunk i overlaps prefill of chunk i+1)."""
        from ray_tpu.ops.paged_attention import page_layout

        _require_paged(self, "DecodeServer")
        if t_request is None:
            t_request = time.monotonic()
        P = len(prompt)
        ps = self.config.page_size
        trace_id = tracing.new_trace_id()
        t_q0 = time.time()
        # prefix-aware reservation FIRST: the cached page count decides
        # how many leading pages prefill can skip shipping entirely
        slot_idx, cached = await self._reserve(prompt, P + max_tokens,
                                               use_prefix=True)
        tracing.record_window("serve.pd.queue", "serve", trace_id,
                              t_q0, time.time(), args={"slot": slot_idx})
        skip_pages = cached // ps
        if getattr(self, "_ship_reader", None) is None:
            self._ship_reader = kv_transfer.ShipReader()
        reader = self._ship_reader
        ship_id = None
        try:
            header = await source.begin(prompt, skip_pages, trace_id,
                                        temperature, top_p, top_k, logprobs)
            ship_id = header["ship_id"]
            layout = header["layout"]
            mine = page_layout(self.cache)
            if layout != mine or header["prompt_len"] != P:
                raise ValueError(
                    f"shipment layout {layout} does not match this decode "
                    f"replica's cache {mine}")
            total_pages = header["total_pages"]
            data_addr = header.get("data_addr")
            have = 0
            installed = header["skip_pages"]
            res: Dict[str, Any] = {"done": False}
            while not res["done"]:
                t_w0 = time.time()
                res = await source.wait(ship_id, have)
                tracing.record_window("serve.pd.prefill", "serve", trace_id,
                                      t_w0, time.time())
                for seg in res["segments"]:
                    t_s0 = time.time()
                    att = await reader.fetch(
                        seg, layout, data_addr,
                        rpc_fetch=lambda oid: source.fetch(ship_id, oid))
                    try:
                        plen = min(P, (seg["page_start"]
                                       + seg["n_pages"]) * ps)
                        self._install_pages(slot_idx, seg["page_start"],
                                            seg["n_pages"], att.blocks, plen)
                    finally:
                        att.close()
                    installed = seg["page_start"] + seg["n_pages"]
                    tracing.record_window(
                        "serve.pd.kv_ship", "serve", trace_id, t_s0,
                        time.time(), args={"pages": seg["n_pages"],
                                           "bytes": seg["nbytes"]})
                have += len(res["segments"])
            if installed != total_pages:
                raise RuntimeError(
                    f"shipment ended at page {installed}/{total_pages}")
        except BaseException:
            self._release_slot(slot_idx)
            if ship_id is not None:
                asyncio.ensure_future(source.drop(ship_id))
            raise
        asyncio.ensure_future(source.drop(ship_id))
        if self.config.prefix_cache and self.page_mgr is not None:
            # installed pages are final — publish them so the NEXT request
            # sharing this prefix ships only ITS suffix
            self.page_mgr.register_prefix(slot_idx, prompt)
        return self._finish_admit(slot_idx, P, max_tokens, eos_id, stream,
                                  temperature, top_p, top_k, logprobs,
                                  int(res["token"]), res.get("logprob"),
                                  t_request, trace_id)

    def _finish_admit(self, slot_idx: int, P: int, max_tokens: int, eos_id,
                      stream: bool, temperature, top_p, top_k,
                      logprobs: bool, first: int,
                      logprob: Optional[float], t_request: float,
                      trace_id: Optional[str]):
        """The tail of a PD admission: build the slot, emit the
        prefill-sampled first token, observe PD TTFT, activate decode."""
        # prompt_ids=None: PD decode requires paged KV while speculation
        # requires the dense cache, so prompt-lookup drafting can never be
        # active on this path
        slot = self._make_slot(P, max_tokens, eos_id, stream, temperature,
                               top_p, top_k, logprobs, prompt_ids=None)
        slot.generated.append(first)
        if logprobs and logprob is not None:
            slot.logprobs.append(float(logprob))
        if slot.stream_queue is not None:
            slot.stream_queue.put_nowait(first)
        slot.first_token.set()
        # the disaggregated path bypasses _admit, so its SLO observation
        # lives here — same histogram, path=pd tag
        self._m_ttft.observe(time.monotonic() - t_request,
                             tags=self._pd_slo_tags())
        finished = max_tokens <= 1 or (eos_id is not None and first == eos_id)
        if finished:
            self._release_slot(slot_idx)
            slot.done_event.set()
            if slot.stream_queue is not None:
                slot.stream_queue.put_nowait(None)
        else:
            # the same join as a colocated prompt's first token, with a
            # host value: the slot decodes from the next chunk dispatched
            self._join(slot_idx, slot, np.int32(first))
            self._ensure_tick_loop()
            if trace_id is not None and tracing.enabled():
                t_act = time.time()

                async def _first_decode_window():
                    # TTFT's tail: activation → the first decode tick
                    # lands token 2 (token 1 was sampled on prefill)
                    while (len(slot.generated) < 2
                           and not slot.done_event.is_set()):
                        await asyncio.sleep(0.002)
                    tracing.record_window("serve.pd.first_decode", "serve",
                                          trace_id, t_act, time.time())
                asyncio.ensure_future(_first_decode_window())
        return slot_idx, slot, finished

    async def generate_with_kv(self, prompt_ids: List[int],
                               source: ShipSource, max_tokens: int = 32,
                               eos_id: Optional[int] = None,
                               temperature: Optional[float] = None,
                               top_p: Optional[float] = None,
                               top_k: Optional[int] = None,
                               logprobs: bool = False,
                               t_request: Optional[float] = None
                               ) -> Dict[str, Any]:
        t0 = time.perf_counter()
        prompt = list(prompt_ids)
        _idx, slot, finished = await self._admit_with_kv(
            prompt, source, max_tokens, eos_id, False, temperature, top_p,
            top_k, logprobs, t_request=t_request)
        ttft = time.perf_counter() - t0
        if not finished:
            await slot.done_event.wait()
            if slot.error is not None:
                raise RuntimeError("decode engine failed") from slot.error
        toks = slot.generated[:max_tokens]
        if eos_id is not None and eos_id in toks:
            toks = toks[:toks.index(eos_id)]
        out = {"tokens": toks, "ttft_s": ttft,
               "total_s": time.perf_counter() - t0}
        if len(toks) > 1:
            # per-token decode latency for the disaggregated path (the
            # colocated path observes inside _note_sync)
            self._m_tpot.observe((out["total_s"] - ttft) / (len(toks) - 1)
                                 * 1e3, tags=self._pd_slo_tags())
        if logprobs:
            out["logprobs"] = slot.logprobs[:len(toks)]
        return out

    def _install_pages(self, slot_idx: int, page_start: int, n_pages: int,
                       blocks, plen: int) -> None:
        """Scatter one shipment segment's page blocks (one a per-page pool,
        [L,Kh,n,ps,D] k and v in the dense layout) into the slot's pool rows
        [page_start, page_start+n_pages). The host arrays alias the shm
        segment (zero-copy all the way from the prefill replica's seal) and
        the device upload reads straight out of it."""
        rows = np.asarray(self.page_mgr.table_slice(
            slot_idx, page_start, n_pages), np.int32)
        self._scatter_pages(slot_idx, rows, blocks, plen)

    def _scatter_pages(self, slot_idx: int, rows, blocks, plen: int) -> None:
        """Pages `rows` of every pool <- `blocks`, and the slot's length.

        The one scatter body runs jitted with the cache DONATED, so XLA
        updates the page arrays in place — an un-jitted `.at[].set` here
        would copy every full pool per admitted request (a transient
        2x-KV-pool HBM spike on the hot path; r5 review)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import scatter_pages

        if getattr(self, "_scatter_jit", None) is None:
            def scatter(cache, new, rows, slot, plen):
                cache = scatter_pages(cache, rows, new, page_major=False)
                return cache.replace(
                    lengths=cache.lengths.at[slot].set(plen))
            self._scatter_jit = jax.jit(scatter, donate_argnums=(0,))
        self.cache = self._scatter_jit(
            self.cache,
            tuple(jnp.asarray(np.asarray(b)) for b in blocks),
            jnp.asarray(rows), jnp.int32(slot_idx), jnp.int32(plen))
        # the upload may alias the shm segment (CPU zero-copy device_put);
        # wait for the scatter so the caller can close the segment safely
        jax.block_until_ready(self.cache)


class PDServer(DecodeServer):
    """Decode-as-orchestrator deployment (ref pd_server.py PDOrchestrator):
    holds the prefill deployment's handle; every generate() streams the
    prompt KV from remote prefill and decodes locally. `prefill` may be a
    serve DeploymentHandle or a direct PrefillServer (in-process tests)."""

    def __init__(self, config: Optional[LLMConfig] = None, params=None,
                 prefill=None):
        super().__init__(config, params)
        _require_paged(self, "PDServer")
        self._prefill = prefill
        self._ship_src: Optional[ShipSource] = None
        self.pd_requests = 0

    def _ship_source(self) -> ShipSource:
        if self._ship_src is None:
            self._ship_src = ShipSource(self._prefill)
        return self._ship_src

    async def generate(self, prompt_ids: List[int], max_tokens: int = 32,
                       eos_id: Optional[int] = None,
                       temperature: Optional[float] = None,
                       top_p: Optional[float] = None,
                       top_k: Optional[int] = None,
                       logprobs: bool = False) -> Dict[str, Any]:
        if self._prefill is None:   # degraded mode: colocated prefill
            return await super().generate(
                prompt_ids, max_tokens, eos_id, temperature=temperature,
                top_p=top_p, top_k=top_k, logprobs=logprobs)
        self.pd_requests += 1
        t_req = time.monotonic()
        kw = dict(temperature=temperature, top_p=top_p, top_k=top_k,
                  logprobs=logprobs)
        return await self.generate_with_kv(
            list(prompt_ids), self._ship_source(), max_tokens, eos_id,
            t_request=t_req, **kw)

    async def generate_stream(self, prompt_ids: List[int],
                              max_tokens: int = 32,
                              eos_id: Optional[int] = None,
                              temperature: Optional[float] = None,
                              top_p: Optional[float] = None,
                              top_k: Optional[int] = None):
        """Streaming rides the same disaggregation: remote prefill, then
        tokens stream from the local decode slot (the inherited path would
        silently prefill on THIS replica — r5 review)."""
        if self._prefill is None:
            async for tok in super().generate_stream(
                    prompt_ids, max_tokens, eos_id, temperature=temperature,
                    top_p=top_p, top_k=top_k):
                yield tok
            return
        self.pd_requests += 1
        t_req = time.monotonic()
        _idx, slot, _fin = await self._admit_with_kv(
            list(prompt_ids), self._ship_source(), max_tokens, eos_id, True,
            temperature, top_p, top_k, False, t_request=t_req)
        emitted = 0
        while emitted < max_tokens:
            tok = await slot.stream_queue.get()
            if tok is None or (eos_id is not None and tok == eos_id):
                break
            emitted += 1
            yield tok
        if slot.error is not None:
            raise RuntimeError("decode engine failed") from slot.error

    def stats(self) -> Dict[str, Any]:
        s = super().stats()
        s["pd_requests"] = self.pd_requests
        s["kv_ship"] = _metrics.kv_ship_counters()
        return s
