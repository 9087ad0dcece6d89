"""LLM serving replica — continuous batching on a jitted decode step
(reference: ray serve LLM examples / serve/llm vLLM integration; re-designed
TPU-first instead of wrapping vLLM's CUDA paged attention).

Design: B decode slots over a PAGED cache (`LLMConfig(paged=True)`, what
every benchmark cell runs): keys and values live in a pool of pages
(`ops/paged_attention.py PagedKVCache`, read by the pallas kernels through
a block table a slot), and `serve/radix_cache.py PageManager` owns which
page belongs to which row, the radix tree of finished prompts' pages that
later prompts share, and eviction. Requests are admitted into free slots
with their pages reserved up front (prefill fills them by chunks), and ONE
jitted decode call advances every active slot each tick — XLA sees the same
program forever, no recompiles, while requests join/leave between ticks
(continuous batching). Sampling is temperature/top-k/top-p on-device. Pages
the tree evicts leave the pool and come back through a collaborator,
`serve/kv_transfer.py DemotionTier`; `close()` gives back what it holds.

The dense slot cache (`paged=False`: `models/llama.py KVCache`, [B, Smax] a
layer with per-row lengths) is what `tp > 1` and `speculate` still need;
nothing else does (ROADMAP D3).

The decode tick is a fused MULTI-TOKEN chunk (Podracer/Anakin lesson —
keep the inner loop on device): a lax.scan runs up to `decode_chunk`
[B, 1] steps — sampling, per-slot EOS/max-token/max-seq-len termination
masking, logprob capture — in one jitted call with ONE host sync per
chunk, so the per-token host round-trip amortizes by N (how much that
buys on a chip is not measured). The loop adapts: chunk 1 while
prefill jobs are queued (continuous batching must admit promptly),
`decode_chunk` in steady-state decode; streaming slots flush their queue
once per chunk, in order.

The loop runs one program AHEAD of what it reads: what a chunk starts from
(each slot's last token, active flag, what is left of its budget and its
row, its eos and sampling parameters: `SlotState`) lives on the device and
is carried from chunk to chunk, a prompt's first token joins it there, and
chunk k+1 is dispatched before chunk k is read (`_tick_loop_inner`). The
host never blocks on the device with nothing queued behind what it waits
for; `stats()["decode"]["read_wait_s"]` says when it did.
"""

import asyncio
import collections
import dataclasses
import json
import logging
import math
import time
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from ray_tpu.serve.kv_transfer import TIER_COUNTERS, DemotionTier
from ray_tpu.util.tracing import PhaseTotals, StallWatch, phase

logger = logging.getLogger(__name__)

# Every stretch of host time on the engine's event-loop thread belongs to one
# of these phases (util.tracing.phase: a profiler annotation `engine.<key>`
# plus stats()["decode"]["phase_s"/"phase_n"]). LOOP_PHASES tile one iteration
# of the tick loop and sum to `loop_s`; NESTED_PHASES are their children,
# mostly inside `yield`: admit_allocate holds evict, which holds demote (one
# entry an eviction pass), which holds demote_stash. Keys carry no dot:
# readers split counter paths on ".".
LOOP_PHASES = ("decode_build", "decode_dispatch", "decode_sync",
               "decode_emit", "prefill_dispatch", "prefill_first_token",
               "yield")
NESTED_PHASES = ("admit_allocate", "evict", "demote", "demote_stash",
                 "restore")
# The two phases that hold a `device_get`: an entry that outlasts its work is
# counted as a stall, and a watchdog samples the process while it lasts
# (util.tracing.StallWatch). Not `yield`: it holds the profiler's stop.
WATCHED_PHASES = ("decode_sync", "prefill_first_token")
# A model with a recurrent state a slot only: the copy of a slot's state
# into a snapshot where its prefill crosses the prompt's last page boundary or
# leaves the tree (inside prefill_dispatch), and out of one at admission
# (inside admit_allocate).
STATE_PHASES = ("state_save", "state_restore")
# A model with sliding-window layers only: the hand-back of window pages a row
# has moved past and the taking of its next ones, before a prefill chunk
# (inside prefill_dispatch) and before a decode chunk (inside decode_dispatch).
# Host bookkeeping and one small table write; nothing is read from the device.
WINDOW_PHASES = ("window_release",)
# entries of the device's window table one write carries
WINDOW_WRITE = 64

SNAPSHOTS_PER_SLOT = 4


class SlotState(NamedTuple):
    """What a decode chunk starts from, per slot, ON THE DEVICE ([B] arrays
    beside the cache; a pytree of jax arrays): carried from chunk to chunk,
    written at `slot` by the join when a prompt's first token exists, never
    rebuilt by the host. `budget` and `room` count DOWN: tokens the request
    may still emit, positions its cache row still has. A slot whose `active`
    is False runs frozen, whatever its other fields hold."""
    last: Any        # int32: the token the next step feeds
    active: Any      # bool
    budget: Any      # int32
    room: Any        # int32
    eos: Any         # int32, -1: none
    temps: Any       # float32
    top_ps: Any      # float32
    top_ks: Any      # int32


@dataclasses.dataclass
class _Chunk:
    """A dispatched decode chunk (or speculative verify) the host has not
    read: its results as device arrays whose copy to the host has started,
    and the host's view at dispatch."""
    seq: int                    # 1-based count of dispatches
    n: Optional[int]            # chunk length; None: a speculative tick
    slots: List[tuple]          # (slot idx, _Slot) the host knew to be live
    out: tuple                  # (toks, n_valid, logp, *touched)
    t_dispatch: float
    drafts: Optional[Dict[int, List[int]]] = None


@dataclasses.dataclass
class LLMConfig:
    preset: str = "tiny"            # LlamaConfig preset name
    max_batch_slots: int = 8        # concurrent decode slots (B)
    max_seq_len: int = 512          # Smax (prompt + generation)
    temperature: float = 0.0        # 0 → greedy (per-request overridable)
    top_k: int = 0                  # 0 → full softmax (per-request overridable)
    top_p: float = 1.0              # nucleus cutoff (per-request overridable;
    #                                 ref: sglang_engine.py:90 top_p)
    param_dtype: str = "bfloat16"
    dtype: Optional[str] = None     # activation dtype override (None = preset)
    seed: int = 0
    # paged KV cache (ops/paged_attention: pallas kernel over a block table;
    # vLLM's memory model). HBM for KV = num_pages·page_size instead of
    # B·max_seq_len, admission reserves prompt+max_tokens pages per request.
    paged: bool = False
    # 64 balances kernel step size (bigger pages -> fewer, fatter DMAs; 128
    # benched fastest on v5e) against allocation granularity (smaller pages
    # waste less HBM per request)
    page_size: int = 64
    num_pages: Optional[int] = None  # default: full (B·ceil(Smax/page)) + 1
    # a model with sliding-window layers: the pages of ITS pool (their keys
    # and values lie apart from the full layers', a row holds a window's
    # worth at most). Default: every slot's budget, and as much again to
    # cache finished prompts' tails.
    num_window_pages: Optional[int] = None
    # a model with a recurrent state a slot: the snapshots of it that the
    # prefix cache keeps (one is a slot's state over again, megabytes).
    # Default: SNAPSHOTS_PER_SLOT a slot.
    num_snapshots: Optional[int] = None
    # Chunked prefill (ref: vLLM chunked prefill / the reference's
    # prefill-decode disaggregation, python/ray/llm/_internal/serve/
    # serving_patterns/prefill_decode/pd_server.py): prompts are fed through
    # the model `prefill_chunk` tokens per engine tick, interleaved with
    # decode steps, so a long prompt never stalls active streams for more
    # than one chunk's compute (VERDICT r3 weak #6).
    prefill_chunk: int = 128
    # Fused multi-token decode (Podracer/Anakin: keep the inner loop on
    # device): lax.scan runs up to this many decode steps per jitted call
    # — sampling, EOS/max-token/max-seq-len termination masking and
    # logprob capture included — with ONE host sync per chunk, so the
    # per-token host round-trip amortizes by N. The tick loop stays at
    # chunk 1 while prefill jobs are queued (admission must not wait N
    # steps) and while speculation is on (the draft check is per-tick),
    # then ramps to this value in steady-state decode. 8 was chosen on an
    # installation that no longer exists and is not re-measured;
    # runtime-adjustable via serve user_config → reconfigure().
    decode_chunk: int = 8
    # Bytes of demoted pages that may be staged (gathered on the device and
    # copied to the host) before the stash's thread has sealed them; over it
    # the engine loop waits for the oldest hand-off. None:
    # kv_transfer.STAGED_CAP_BYTES.
    # A deployment whose ONE admission evicts more than that raises it (a
    # 28k-token prompt of 557 KB pages is 250 MB), or every such admission
    # stalls the loop at the pace of the stash's disk.
    staged_cap_bytes: Optional[int] = None
    # Prefix caching (paged mode only; ref: the reference's sglang engine
    # serves RadixAttention prefix reuse): full prompt pages are
    # content-addressed and shared across requests with refcounts — a
    # repeated prompt prefix skips its prefill entirely (TTFT win).
    prefix_cache: bool = True
    # Prompt-lookup speculative decoding (dense cache only; ref: the
    # reference serves draft-model speculation through its vLLM engine
    # config — here the draft is FREE: the continuation of the most recent
    # n-gram match in the request's own prompt+output, verified in ONE
    # [B, K+1] forward. Decode is HBM-bound on TPU (the K+1-position
    # forward re-reads the same cache a [B, 1] step would), so verification
    # costs little; on repetitive text K tokens land per tick instead
    # of 1. Greedy slots stay EXACT (an accepted draft token equals the
    # argmax target by construction); sampled slots take one token per
    # tick from the unchanged position-0 sampler.
    speculate: int = 0              # K draft tokens per tick (0 = off)
    spec_ngram: int = 3             # n-gram length for the prompt lookup
    # Tensor-parallel serving (BASELINE config #3: one inference replica
    # spanning a v5e-8 slice). tp>1 builds a {"tp": tp} mesh, shards
    # params with the canonical llama_rules (attention heads + ffn over
    # tp) and the KV cache on its kv-head axis, then lets GSPMD partition
    # the SAME jitted prefill/decode programs — XLA inserts the
    # all-reduces where wo/w_down contract the tp axis; no per-op
    # collectives in this file. Dense cache only: the paged pallas
    # kernel would need an explicit shard_map, the dense path is pure
    # XLA and auto-partitions.
    tp: int = 1
    # extra LlamaConfig kwargs applied over the preset (e.g. vocab_size for
    # a tokenizer whose id space outgrows the preset's)
    model_overrides: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class _Slot:
    request_id: int
    prompt_len: int
    max_tokens: int
    generated: List[int]
    done_event: asyncio.Event
    stream_queue: Optional[asyncio.Queue] = None
    eos_id: Optional[int] = None
    error: Optional[BaseException] = None
    # per-request sampling params (None → server config default)
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    want_logprobs: bool = False
    logprobs: List[float] = dataclasses.field(default_factory=list)
    # full context (prompt + generated) for prompt-lookup drafting
    prompt_ids: List[int] = dataclasses.field(default_factory=list)
    # incremental prompt-lookup state (greedy slots, speculate>0 only):
    # ctx mirrors prompt+generated; spec_index maps each n-gram WITH a
    # known continuation to that continuation's start — O(1) draft lookup
    # per tick instead of an O(context) scan on the event loop
    ctx: List[int] = dataclasses.field(default_factory=list)
    spec_index: Dict = dataclasses.field(default_factory=dict)
    # set when the first token exists (prefill complete); TTFT boundary
    first_token: asyncio.Event = dataclasses.field(
        default_factory=asyncio.Event)
    # decode steps dispatched for this slot whose tokens the host has not
    # read, and `max_tokens` as the device was told at the join (the host's
    # may shrink later: a consumer that walked away)
    ahead: int = 0
    joined_max: int = 0


@dataclasses.dataclass
class _PrefillJob:
    """A prompt being fed through the model chunk-by-chunk by the engine."""
    slot_idx: int
    slot: _Slot
    prompt: "np.ndarray"
    pos: int = 0


class LLMServer:
    """Deployment class: `generate(prompt_ids, max_tokens)` → token ids.

    Works on token ids; wrap with a tokenizer deployment for text. Designed
    to run as `@serve.deployment(ray_actor_options={"num_tpus": 1})`.
    """

    def __init__(self, config: Optional[LLMConfig] = None, params=None):
        import jax
        import jax.numpy as jnp
        from ray_tpu.models.llama import (KVCache, Llama, LlamaConfig,
                                          split_rotary_pairs)

        self.config = cfg = config or LLMConfig()
        preset = getattr(LlamaConfig, cfg.preset)
        overrides = dict(max_seq_len=cfg.max_seq_len,
                         param_dtype=getattr(jnp, cfg.param_dtype))
        if cfg.dtype is not None:
            overrides["dtype"] = getattr(jnp, cfg.dtype)
        if cfg.model_overrides:
            overrides.update(cfg.model_overrides)
        self.model_cfg = preset(**overrides)
        if self.model_cfg.n_experts > 0:
            # Serving must be DROPLESS: with the training default
            # capacity_factor, a token's expert output could be zeroed
            # because of which OTHER requests share the decode batch —
            # same prompt, different completions under load. cf = E/K makes
            # C = ceil(cf·K·S/E) = S, so every token always gets all its
            # top-k experts regardless of co-batched traffic.
            import dataclasses as _dc
            dropless = self.model_cfg.n_experts / self.model_cfg.moe_top_k
            if self.model_cfg.capacity_factor < dropless:
                self.model_cfg = _dc.replace(self.model_cfg,
                                             capacity_factor=dropless)
        self.model = Llama(self.model_cfg)
        # recurrent state a slot beside the pages (linear-attention layers,
        # or a state-space mixer in every block)
        self._stateful = self.model_cfg.n_state_layers > 0
        # sliding-window layers: a second pool of pages, with its own table
        self._windowed = self.model_cfg.n_window_layers > 0
        self._phases = PhaseTotals(
            "engine", LOOP_PHASES + NESTED_PHASES
            + (STATE_PHASES if self._stateful else ())
            + (WINDOW_PHASES if self._windowed else ()),
            watch=WATCHED_PHASES)
        # the last records the loop's watchdog filed (stats()["stalls"])
        self._stalls: "collections.deque[dict]" = collections.deque(maxlen=8)
        B = cfg.max_batch_slots
        key = jax.random.PRNGKey(cfg.seed)
        if cfg.tp > 1:
            if cfg.paged:
                raise ValueError(
                    "tp>1 requires paged=False: the paged pallas kernel "
                    "does not auto-partition under GSPMD (dense decode "
                    "attention does)")
            if self.model_cfg.n_kv_heads % cfg.tp:
                raise ValueError(
                    f"tp={cfg.tp} must divide n_kv_heads="
                    f"{self.model_cfg.n_kv_heads} (the KV cache shards on "
                    f"its kv-head axis)")
            from ray_tpu.parallel.mesh import make_mesh
            from ray_tpu.parallel.sharding import llama_rules, shard_tree
            if cfg.tp > len(jax.devices()):
                raise ValueError(f"tp={cfg.tp} but only "
                                 f"{len(jax.devices())} devices visible")
            self.mesh = make_mesh({"tp": cfg.tp},
                                  devices=jax.devices()[:cfg.tp])
            if params is None:
                # born sharded: tp exists for models that do NOT fit one
                # chip, so init must never materialize the full tree on
                # device 0 first — jit with out_shardings allocates each
                # shard on its owner directly
                dummy = jnp.zeros((1, 8), jnp.int32)
                abstract = jax.eval_shape(self.model.init, key, dummy)
                shardings = llama_rules().tree_shardings(abstract, self.mesh)
                self.params = jax.jit(self.model.init,
                                      out_shardings=shardings)(key, dummy)
            else:
                # host → per-shard transfers (no single-device staging)
                self.params = shard_tree(params, self.mesh, llama_rules())
        else:
            self.mesh = None
            if params is None:
                params = self.model.init(key, jnp.zeros((1, 8), jnp.int32))
            self.params = jax.device_put(params)
        # The pair the jitted programs run. `params`, `model` and `model_cfg`
        # stay what the caller gave (a reference takes them as a pair that
        # agrees with itself); for a model whose rotary is interleaved the
        # programs take a tree with the rotary's pairs split once, here, and
        # the rotate-half form (models/llama.py split_rotary_pairs): held
        # BESIDE the given one, only the rebuilt leaves are new arrays. For
        # every other model the same objects.
        self._run_params, run_cfg = split_rotary_pairs(self.params,
                                                       self.model_cfg)
        self._run_model = (self.model if run_cfg is self.model_cfg
                           else Llama(run_cfg))
        # projections split at load (stats()["decode"]): tells a trace
        # without the interleaved form's relayouts from another model's
        self._rotary_split = sum(
            ran is not given for ran, given in zip(
                jax.tree_util.tree_leaves(self._run_params),
                jax.tree_util.tree_leaves(self.params)))
        if cfg.speculate > 0 and cfg.paged:
            # checked BEFORE the page pool below: a config error must not
            # cost a multi-GB HBM allocation first
            raise ValueError(
                "speculate requires paged=False: the paged decode kernel "
                "is single-position; the dense cache path verifies [B, K+1] "
                "windows natively (set paged=False or speculate=0)")
        if self.model_cfg.index_topk and not cfg.paged:
            raise ValueError(
                "a model with learned sparse attention (index_topk > 0) "
                "needs paged=True: its indexer keys live in the paged "
                "cache's third pool")
        if self._stateful and not cfg.paged:
            raise ValueError(
                "a model with a recurrent state (full_attn_every > 0 or "
                "ssm_heads > 0) needs paged=True: a slot's state and its "
                "snapshots live in the paged cache")
        if self._windowed and not cfg.paged:
            raise ValueError(
                "a model with sliding-window layers (layer_types) needs "
                "paged=True: their keys and values live in the paged "
                "cache's window pool (so it cannot speculate either: "
                "speculate needs paged=False)")
        # pages that leave the pool and come back (kv_transfer.DemotionTier);
        # None without a prefix cache
        self._tier: Optional[DemotionTier] = None
        if cfg.paged:
            from ray_tpu.ops.paged_attention import PagedKVCache
            from ray_tpu.serve.radix_cache import PageManager
            mc = self.model_cfg
            max_pages = -(-cfg.max_seq_len // cfg.page_size)
            num_pages = cfg.num_pages or (B * max_pages + 1)
            # tiered KV (ISSUE 19): the radix tree demotes LRU-evicted
            # prefix pages into the tier's stash (shm → disk ladder) and
            # restores them on a later match instead of recomputing prefill
            hooks = {}
            if cfg.prefix_cache:
                self._tier = DemotionTier(
                    lambda: self.cache, self._phases,
                    lambda *a: self.page_mgr.demotion_failed(*a),
                    cfg.staged_cap_bytes)
                # A model with state demotes nothing: eviction is leaf first,
                # so the node that holds a chain's snapshot goes before the
                # pages above it, and pages with no snapshot below them can
                # serve no later prompt (radix_cache.py): extracting them
                # would fill the stash, and its disk, with bytes nothing reads
                # nor does one with a window pool: a sliding layer's pages are
                # not carried to the stash, and a chain restored without them
                # could not be resumed from
                if not self._stateful and not self._windowed:
                    hooks = self._tier.hooks()
            # a model with state: every request in flight saves one snapshot
            # and the newest of as many conversations again have to outlive
            # them, so four a slot (one snapshot is a few thousand tokens'
            # worth of keys and values: one a page is out of the question,
            # one a prompt is cheap)
            snapshots = ((cfg.num_snapshots or SNAPSHOTS_PER_SLOT * B)
                         if self._stateful and cfg.prefix_cache else 0)
            window = {}
            if self._windowed:
                # what a row holds at most: the pages under a window's keys
                # and under what one program adds to them (a prefill chunk,
                # or the decode steps dispatched and not yet read)
                ahead = max(cfg.prefill_chunk, 3 * cfg.decode_chunk + 2)
                budget = (mc.sliding_window + ahead - 2) // cfg.page_size + 2
                window = dict(
                    window=mc.sliding_window, window_budget=budget,
                    window_pages=(cfg.num_window_pages
                                  or 2 * B * min(budget, max_pages) + 1))
            self.page_mgr = PageManager(
                num_pages, cfg.page_size, B, max_pages,
                prefix_cache=cfg.prefix_cache, phases=self._phases,
                snapshots=snapshots, **window, **hooks)
            # the cache follows the model's schema: a model with an indexer
            # gets the third per-page pool (and the token-major layout), one
            # with linear layers pools for its full layers only (one with a
            # mixer in every block: for every layer), a state a slot and the
            # snapshot pool
            pools = {}
            if self._stateful:
                pools["linear"] = dict(mc.state_schema(),
                                       snapshots=max(snapshots, 1))
            if self._windowed:
                pools["window"] = dict(layers=mc.n_window_layers,
                                       num_pages=window["window_pages"])
            self.cache = PagedKVCache.init(
                mc.n_layers - mc.n_linear_layers - mc.n_window_layers,
                mc.n_kv_heads, mc.head_dim,
                num_pages, cfg.page_size, B, max_pages, dtype=mc.dtype,
                index_dim=mc.index_dim if mc.index_topk else 0, **pools)
        else:
            self.page_mgr = None
            if self.mesh is not None:
                # born sharded on the kv-head axis ([B, Smax, Kh, D]) to
                # match the tp-sharded wk/wv projections — KV for a head
                # never crosses chips, and the full-size cache is never
                # staged on one device (same OOM argument as params)
                from jax.sharding import NamedSharding, PartitionSpec
                kv_s = NamedSharding(self.mesh,
                                     PartitionSpec(None, None, "tp", None))
                rep = NamedSharding(self.mesh, PartitionSpec())
                abstract = jax.eval_shape(
                    lambda: KVCache.init(self.model_cfg, B, cfg.max_seq_len))
                out_sh = jax.tree_util.tree_map(
                    lambda leaf: kv_s if leaf.ndim == 4 else rep, abstract)
                self.cache = jax.jit(
                    lambda: KVCache.init(self.model_cfg, B, cfg.max_seq_len),
                    out_shardings=out_sh)()
            else:
                self.cache = KVCache.init(self.model_cfg, B, cfg.max_seq_len)
        # slot idx -> request state, from the join of its first token (which
        # the host may not have read yet: `generated` is then empty)
        self._active: Dict[int, _Slot] = {}
        # the device-resident slot state every decode chunk is carried
        # through (SlotState), the chunks dispatched and not read (oldest
        # first), and the first tokens sampled and joined on the device
        # that the host has not read: (seq, slot idx, slot, token, logprob),
        # `seq` the dispatch count at the join (later chunks hold the slot)
        self._slots = self._idle_slots()
        self._inflight: "collections.deque[_Chunk]" = collections.deque()
        self._reading: Optional[_Chunk] = None    # the chunk `_read_chunk` is in
        self._first_pending = collections.deque()
        self._n_dispatched = 0
        self._t_read = 0.0
        # speculative-decoding accounting (stats()/serving bench)
        self._spec = None
        self._spec_stats = {"spec_ticks": 0, "decode_ticks": 0,
                            "drafted": 0, "accepted": 0}
        # the engine loop's own accounting (stats()["decode"]), monotonic
        # over the server's life and read as window deltas. ONE host sync
        # per chunk is the whole perf story, so it is a recorded counter,
        # not an inference — decode_bench.py asserts on it
        self._decode_stats = {
            "host_syncs": 0, "tokens": 0, "chunk_s_total": 0.0,
            "chunk_sizes": {}, "loop_s": 0.0, "ticks": 0,
            "decode_steps": 0, "active_slot_syncs": 0,
            "prefill_chunks": 0, "prefill_tokens": 0,
            "prefill_padded_tokens": 0,
            # prefill chunks that started past position 0, the sum over them
            # of the keys the chunk's last real query reaches (start + n),
            # and of the (query, key) pairs their real queries see: times
            # 4 x heads x head_dim, what their attention had to compute
            "continuation_chunks": 0, "continuation_reach_keys": 0,
            "continuation_query_keys": 0,
            "admitted": 0, "slot_wait_s": 0.0,
            "slot_wait_max_s": 0.0,
            # the loop runs one program ahead of what it reads: chunks
            # dispatched while an unread one was in flight, first tokens
            # that joined their slot as device values, and seconds the host
            # was blocked on a read with NO program queued behind it
            "run_ahead_chunks": 0, "joined_on_device": 0, "read_wait_s": 0.0}
        # what the learned selection and the expert product did, counted on
        # the host at the syncs that are there (stats()["sparse"], ["moe"]):
        # a decode row's context is known without asking the device
        self._sparse_stats = {"context_keys": 0, "selected_keys": 0,
                              "scored_keys": 0, "decode_rows": 0,
                              "dense_rows": 0}
        if self.model_cfg.index_topk and cfg.paged:
            from ray_tpu.ops.paged_attention import index_block_keys
            # keys a step of the scoring kernel's walk covers
            self._index_block = index_block_keys(
                cfg.page_size, self.cache.block_tables.shape[1])
        self._moe_stats = {"routed_rows": 0, "computed_rows": 0,
                           "decode_layer_calls": 0,
                           "decode_experts_touched": 0}
        self._state_stats = {"snapshot_copies": 0, "restore_copies": 0}
        # sliding layers: the (query, key) pairs the real queries of
        # continuation chunks see in ONE of them, and the keys decode rows
        # see in one of them and in one full layer, summed over rows and steps
        self._window_stats = {"window_query_keys": 0, "decode_window_keys": 0,
                              "decode_full_keys": 0, "window_table_writes": 0}
        # the last decode syncs and continuation chunks, (time.monotonic(),
        # what the sync or chunk added to the sliding layers' count, to the
        # full layers'): a reader that times a slice of a run needs the
        # slice's own counts (as `_moe_recent`)
        self._window_recent = {
            "recent_decode_syncs": collections.deque(maxlen=4096),
            "recent_continuations": collections.deque(maxlen=4096)}
        from ray_tpu.models.llama import _n_moe_layers
        from ray_tpu.models.moe import grouped_product
        mc = self.model_cfg
        self._moe_layers = _n_moe_layers(mc)
        self._moe_grouped = self._moe_layers > 0 and grouped_product(
            mc.n_experts, mc.moe_top_k)
        # the last decode syncs of a grouped product, (time.monotonic() at
        # the sync, layer calls, experts touched): how many experts a call
        # reaches moves with what the streams are saying, so a reader that
        # times a slice of a run (a profiler's few seconds) needs the
        # slice's own count and not the run's mean
        self._moe_recent = collections.deque(maxlen=4096)
        from ray_tpu.util import metrics as _metrics
        # serving SLO histograms (TTFT / TPOT / occupancy / KV utilization),
        # tagged by engine flavor so paged and dense replicas in one process
        # keep separate series; stats()["slo"] summarizes via
        # metrics.histogram_summary. TTFT/TPOT also carry a request-path
        # tag: `local` for colocated prefill+decode, `pd` for requests
        # whose prompt KV arrived from a prefill replica (pd.py observes
        # those — the disaggregated path never passes through _admit)
        self._slo_tags = {"engine": ("paged" if self.page_mgr is not None
                                     else "dense"),
                          "path": "local"}
        self._m_ttft = _metrics.get_or_create(
            _metrics.Histogram, "serve_ttft_s",
            "time to first token: admit → first emitted token (s)",
            boundaries=[0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10],
            tag_keys=("engine", "path"))
        self._m_tpot = _metrics.get_or_create(
            _metrics.Histogram, "serve_tpot_ms",
            "per-token decode latency: host-sync wall time / tokens (ms)",
            boundaries=[0.5, 1, 2, 5, 10, 20, 50, 100, 200],
            tag_keys=("engine", "path"))
        self._m_occupancy = _metrics.get_or_create(
            _metrics.Histogram, "serve_batch_occupancy",
            "active slots / batch capacity, sampled per decode sync",
            boundaries=[0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0],
            tag_keys=("engine",))
        self._m_kv_util = _metrics.get_or_create(
            _metrics.Histogram, "serve_kv_page_util",
            "KV pages in use / page pool size, sampled per decode sync",
            boundaries=[0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0],
            tag_keys=("engine",))
        # windowed SLO reads for the fleet autoscaler: each slo_snapshot()
        # call summarizes only the observations since the previous call
        self._slo_window_state = {}
        self._free = list(range(B))
        self._req_counter = 0
        self._tick_task = None
        self._sample_key = key
        self._prefill_q: "collections.deque[_PrefillJob]" = collections.deque()
        # signaled whenever capacity frees (slot or pages) — admission waits
        # on this instead of polling (VERDICT r3 weak #6: 5 ms busy-poll)
        self._capacity_event = asyncio.Event()
        self._build_fns()

    # -- jitted programs -----------------------------------------------------
    def _build_fns(self):
        import jax
        import jax.numpy as jnp
        from ray_tpu.models.llama import KVCache

        cfg = self.config
        model = self._run_model
        # a grouped expert product reads the weights of the experts its rows
        # reach; how many that is only the device knows, so the decode chunk
        # hands the count back with its tokens (the same sync)
        count_touched = self._moe_grouped
        stateful = self._stateful
        windowed = self._windowed

        def sample(logits, key, temps, top_ps, top_ks, want_logp):
            """Per-request greedy / temperature / top-k / top-p (nucleus)
            next-token choice, one compiled program for every mix — params
            are traced [B] arrays, not compile-time constants (ref:
            sglang_engine.py:90 serves per-request top_p the same way).
            The sort/cumsum nucleus machinery runs under lax.cond so an
            all-greedy batch (the default) pays one argmax, not
            O(B·V log V) per token; `want_logp` is compile-time (two jit
            variants), so log_softmax only runs when a slot asked for
            logprobs. Returns (next_token [B], logprob-or-zeros [B])."""
            V = logits.shape[-1]
            logits = logits.astype(jnp.float32)
            greedy = jnp.argmax(logits, axis=-1)

            def hot(_):
                scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
                sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
                # top-k cutoff: value of the k-th largest (k==0 → keep all)
                k = jnp.where(top_ks > 0, top_ks, V).astype(jnp.int32)
                kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None],
                                          axis=-1)
                keep = scaled >= kth
                # top-p: smallest leading set of the sorted probs with mass
                # ≥ top_p — position j survives iff cum[j-1] < top_p
                probs = jax.nn.softmax(sorted_desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                kept = jnp.concatenate(
                    [jnp.ones_like(cum[:, :1], bool),
                     cum[:, :-1] < top_ps[:, None]], axis=-1)
                n_keep = kept.sum(axis=-1).astype(jnp.int32)
                pth = jnp.take_along_axis(sorted_desc, (n_keep - 1)[:, None],
                                          axis=-1)
                masked = jnp.where(keep & (scaled >= pth), scaled, -jnp.inf)
                return jax.random.categorical(key, masked, axis=-1)

            sampled = jax.lax.cond(jnp.any(temps > 0), hot,
                                   lambda _: greedy, None)
            nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
            if want_logp:
                logp_full = jax.nn.log_softmax(logits, axis=-1)
                logp = jnp.take_along_axis(logp_full, nxt[:, None],
                                           axis=-1)[:, 0]
            else:
                logp = jnp.zeros(nxt.shape, jnp.float32)
            return nxt, logp

        def prefill_paged(params, cache, tokens, slot, start_len, true_end,
                          chunk_local):
            """Paged prefill of ONE CHUNK: the row's table was set at
            admission; run tokens [start_len, true_end) through the model
            (writes pages in-place). `chunk_local` (static) marks a fresh
            row's FIRST chunk — exact with chunk-only causal attention, no
            full-row page gather. The returned logits row is only
            meaningful on the final chunk."""
            row_tables = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1, 0)
            row_view = cache.replace(block_tables=row_tables,
                                     lengths=start_len[None])
            if stateful:
                return prefill_stateful(params, cache, row_view, tokens, slot,
                                        start_len, true_end, chunk_local)
            if windowed:
                return prefill_windowed(params, cache, row_view, tokens, slot,
                                        start_len, true_end, chunk_local)
            logits, new_row = model.apply(params, tokens, cache=row_view,
                                          paged_chunk_local=chunk_local)
            new_cache = cache.with_pools(new_row.pools()).replace(
                lengths=cache.lengths.at[slot].set(true_end))
            return new_cache, logits[0, true_end - start_len - 1]

        def prefill_stateful(params, cache, row_view, tokens, slot, start_len,
                             true_end, chunk_local):
            """`prefill_paged` for a model with linear layers: the row view
            holds the slot's own state and convolution inputs (a fresh row
            starts from zeros whatever the slot holds), the bucket's padding
            past `true_end` leaves them where the last real token put them,
            and both go back into the slot in place."""
            take = lambda xs: tuple(
                jax.lax.dynamic_slice_in_dim(x, slot, 1, 0) for x in xs)
            put = lambda xs, rows: tuple(
                jax.lax.dynamic_update_slice_in_dim(x, r, slot, 0)
                for x, r in zip(xs, rows))
            row_view = row_view.replace(state=take(cache.state),
                                        conv=take(cache.conv))
            # the head runs on the row that is returned, not on the bucket
            (logits, new_row), seen = model.apply(
                params, tokens, cache=row_view, paged_chunk_local=chunk_local,
                n_valid=(true_end - start_len)[None],
                logits_at=(true_end - start_len - 1)[None],
                mutable=["moe_stats"])
            new_cache = cache.with_pools(new_row.pools()).replace(
                lengths=cache.lengths.at[slot].set(true_end),
                state=put(cache.state, new_row.state),
                conv=put(cache.conv, new_row.conv),
                held_pairs=cache.held_pairs + sown(seen, "held_pairs"))
            return new_cache, logits[0, 0]

        def prefill_windowed(params, cache, row_view, tokens, slot, start_len,
                             true_end, chunk_local):
            """`prefill_paged` for a model with sliding layers: the row view
            has the slot's row of the window table too, both pools come back,
            and the bank's count of the pairs it held is kept."""
            row_view = row_view.replace(win_tables=jax.lax.dynamic_slice_in_dim(
                cache.win_tables, slot, 1, 0))
            (logits, new_row), seen = model.apply(
                params, tokens, cache=row_view, paged_chunk_local=chunk_local,
                n_valid=(true_end - start_len)[None], mutable=["moe_stats"])
            new_cache = cache.with_pools(new_row.pools()).merge_window(
                new_row.window_view()).replace(
                lengths=cache.lengths.at[slot].set(true_end),
                held_pairs=cache.held_pairs + sown(seen, "held_pairs"))
            return new_cache, logits[0, true_end - start_len - 1]

        def sown(seen, name):
            """What the expert banks sowed under `name` this call, summed
            over the layers."""
            flat = jax.tree_util.tree_flatten_with_path(seen)[0]
            return sum(v for path, v in flat
                       if any(getattr(k, "key", None) == name for k in path))

        def prefill_row(params, cache, tokens, slot, start_len, true_end):
            """Write one CHUNK of a (padded) prompt's KV into `slot`'s row;
            tokens: [1, C] padded to a bucket, covering prompt positions
            [start_len, true_end). `slot`/`start_len`/`true_end` are traced
            (one compile per chunk bucket, not per slot or offset). The
            returned logits row is only meaningful on the final chunk."""
            row_cache = KVCache(
                k=tuple(jax.lax.dynamic_slice_in_dim(c, slot, 1, 0)
                        for c in cache.k),
                v=tuple(jax.lax.dynamic_slice_in_dim(c, slot, 1, 0)
                        for c in cache.v),
                length=start_len[None])
            logits, new_row = model.apply(params, tokens, cache=row_cache)
            k = tuple(jax.lax.dynamic_update_index_in_dim(c, nc[0], slot, 0)
                      for c, nc in zip(cache.k, new_row.k))
            v = tuple(jax.lax.dynamic_update_index_in_dim(c, nc[0], slot, 0)
                      for c, nc in zip(cache.v, new_row.v))
            length = cache.length.at[slot].set(true_end)
            last = logits[0, true_end - start_len - 1]
            return KVCache(k=k, v=v, length=length), last

        def decode_chunk(params, cache, state, key, want_logp, n):
            """`n` decode steps entirely ON DEVICE: lax.scan over the same
            [B, 1] forward + sample() the per-step loop ran, with per-slot
            termination folded into the scan — a slot stops the step it
            hits its EOS id, its token budget, or its cache row's capacity,
            and stopped slots stay frozen (length pinned, last token
            pinned) while the rest continue.

            `state` (SlotState) is what the chunk starts from and what it
            hands on: the scan carries `last`, `active`, `budget` and
            `room` and the chunk returns them, so the next chunk is
            dispatched from this one's result with nothing read by the
            host in between; `eos` and the sampling parameters ride
            through unchanged (the join writes them). Cache and state are
            both donated.

            Returns (cache, state', tokens [B, n], n_valid [B], logps
            [B, n], key'): tokens[i, j] is valid iff j < n_valid[i] —
            termination is a prefix property. A model on the grouped expert
            product appends experts_touched [n]: over its layers, the
            experts each step's rows reached. Key discipline matches the
            host loop exactly (one jax.random.split per step, final carried
            key handed back), so a chunk of n is bit-identical to n
            per-step ticks — parity-tested in tests/test_llm_decode_chunk.py.

            Steps after a slot stops still write one KV entry at its
            frozen length (masked on read, overwritten on slot reuse) —
            the same contract inactive slots already had under the
            per-step loop, for both cache layouts; a slot that stopped in
            the chunk before this one and is not yet released runs so
            through all of it."""
            eos_ids = state.eos
            # an idle slot keeps the parameters of its last request: it
            # must not send an all-greedy batch down the sorting sampler
            temps = jnp.where(state.active, state.temps, 0.0)

            def one_step(carry, _):
                cache, last, active, budget, room, emitted, key = carry
                key, sub = jax.random.split(key)
                touched = ()
                if stateful or windowed:
                    # a slot that does not decode this step (idle, finished,
                    # or still prefilling) keeps its recurrent state, and its
                    # row's pairs are not counted as a real token's
                    (logits, new_cache), seen = model.apply(
                        params, last[:, None], cache=cache,
                        n_valid=active.astype(jnp.int32),
                        mutable=["moe_stats"])
                    if count_touched:
                        touched = (sown(seen, "experts_touched"),)
                    new_cache = new_cache.replace(
                        held_pairs=cache.held_pairs + sown(seen, "held_pairs"))
                elif count_touched:
                    (logits, new_cache), seen = model.apply(
                        params, last[:, None], cache=cache,
                        mutable=["moe_stats"])
                    touched = (sum(jax.tree_util.tree_leaves(seen)),)
                else:
                    logits, new_cache = model.apply(params, last[:, None],
                                                    cache=cache)
                nxt, logp = sample(logits[:, -1, :], sub, temps,
                                   state.top_ps, state.top_ks, want_logp)
                step = active.astype(jnp.int32)
                emitted, budget, room = (emitted + step, budget - step,
                                         room - step)
                done = (nxt == eos_ids) | (budget <= 0) | (room <= 0)
                still = active & ~done
                # slots not active THIS step must not advance their row
                if cfg.paged:
                    new_cache = new_cache.replace(lengths=jnp.where(
                        active, new_cache.lengths, cache.lengths))
                else:
                    new_cache = KVCache(
                        k=new_cache.k, v=new_cache.v,
                        length=jnp.where(active, new_cache.length,
                                         cache.length))
                last = jnp.where(still, nxt, last)
                return ((new_cache, last, still, budget, room, emitted, key),
                        (nxt, logp) + touched)

            init = (cache, state.last, state.active, state.budget,
                    state.room, jnp.zeros_like(state.last), key)
            ((cache, last, active, budget, room, n_valid, key),
             (toks, logps, *touched)) = jax.lax.scan(
                one_step, init, None, length=n)
            state = state._replace(last=last, active=active, budget=budget,
                                   room=room)
            return (cache, state, toks.T, n_valid, logps.T, key, *touched)

        def join(state, ints, floats, token):
            """Slot `ints[0]` starts decoding from `token`, its request's
            first (a device value straight from `_sample_first`, or a host
            value where the prompt was prefilled elsewhere): ints =
            [slot, budget, room, eos, top_k] with budget and room what is
            left AFTER that token, floats = [temperature, top_p]. A first
            token that already ends the request (its eos, nothing left of
            budget or row) leaves the slot inactive; so does a budget of 0
            sent to take a slot out again."""
            i, budget, room, eos = ints[0], ints[1], ints[2], ints[3]
            live = (budget > 0) & (room > 0) & (token != eos)
            return SlotState(
                last=state.last.at[i].set(token),
                active=state.active.at[i].set(live),
                budget=state.budget.at[i].set(budget),
                room=state.room.at[i].set(room),
                eos=state.eos.at[i].set(eos),
                temps=state.temps.at[i].set(floats[0]),
                top_ps=state.top_ps.at[i].set(floats[1]),
                top_ks=state.top_ks.at[i].set(ints[4]))

        def spec_step(params, cache, state, drafts, key, want_logp):
            """Verify K drafts + emit a bonus token in ONE [B, K+1] forward.

            Position 0 is each slot's last emitted token, `state.last` (its
            KV is written at the row's length, same lag-by-one contract as
            decode_step); `drafts` [B, K] are prompt-lookup drafts. Greedy
            targets tgt[:, j] = argmax of position j's logits; draft j+1
            is accepted iff it equals tgt[:, j], so every accepted token
            IS the token step-by-step greedy decode would have produced
            — exactness is structural, not probabilistic. n_emit =
            accepted run + 1 bonus for greedy slots; sampled slots take
            position 0 through the unchanged sample() policy and advance
            by one. Row lengths advance by n_emit, so KV written for
            rejected positions sits past `length`: masked on read
            (decode_attention's absolute-position mask) and overwritten
            by the next tick's [length, length+K] write before it can
            ever become readable.

            The slot state moves as the host's emit loop will: a slot takes
            its n_emit tokens up to the first that ends it (eos, budget,
            row), and that one is its `last`."""
            tokens = jnp.concatenate([state.last[:, None], drafts], axis=1)
            active_mask = state.active
            temps = jnp.where(active_mask, state.temps, 0.0)
            logits, new_cache = model.apply(params, tokens, cache=cache)
            logits = logits.astype(jnp.float32)
            nxt0, logp0 = sample(logits[:, 0, :], key, temps, state.top_ps,
                                 state.top_ks, want_logp)
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
            greedy = temps <= 0.0
            match = tokens[:, 1:] == tgt[:, :-1]                 # [B, K]
            n_acc = jnp.cumprod(match.astype(jnp.int32),
                                axis=-1).sum(axis=-1)
            n_emit = jnp.where(greedy & active_mask, n_acc + 1, 1)
            emit = tgt.at[:, 0].set(jnp.where(greedy, tgt[:, 0], nxt0))
            if want_logp:
                lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                         emit[:, :, None], axis=-1)[..., 0]
                lp = lp.at[:, 0].set(jnp.where(greedy, lp[:, 0], logp0))
            else:
                lp = jnp.zeros(emit.shape, jnp.float32)
            length = jnp.where(active_mask, cache.length + n_emit,
                               cache.length)
            new_cache = KVCache(k=new_cache.k, v=new_cache.v, length=length)
            took = jnp.arange(1, emit.shape[1] + 1)[None, :]     # [1, K+1]
            ends = ((emit == state.eos[:, None])
                    | (took >= state.budget[:, None])
                    | (took >= state.room[:, None])) & (
                        took <= n_emit[:, None])
            done = ends.any(axis=-1)
            taken = jnp.where(active_mask, jnp.where(
                done, jnp.argmax(ends, axis=-1) + 1, n_emit), 0)
            last = jnp.take_along_axis(
                emit, jnp.maximum(taken - 1, 0)[:, None], axis=-1)[:, 0]
            state = state._replace(
                last=jnp.where(active_mask, last, state.last),
                active=active_mask & ~done,
                budget=state.budget - taken, room=state.room - taken)
            return new_cache, state, emit, n_emit, lp

        if cfg.paged:
            self._prefill = jax.jit(prefill_paged, donate_argnums=(1,),
                                    static_argnums=(6,))
        else:
            self._prefill = jax.jit(prefill_row, donate_argnums=(1,))
            if cfg.speculate > 0:
                self._spec = jax.jit(spec_step, donate_argnums=(1, 2),
                                     static_argnums=(5,))
        # one compiled variant per (want_logp, chunk length); chunk lengths
        # are power-of-two bucketed by _chunk_len so the variant count stays
        # O(log decode_chunk), and n=1 IS the old per-step program
        self._decode_chunk = jax.jit(decode_chunk, donate_argnums=(1, 2),
                                     static_argnums=(4, 5))
        # compiled here and not at the first admission: nothing may compile
        # once a replica serves (a budget of 0 takes idle slot 0 out again)
        self._join_fn = jax.jit(join, donate_argnums=(0,))
        self._leave(0)
        if cfg.paged:
            # [slot, length, *row]: one small transfer a write
            self._set_row_fn = jax.jit(
                lambda tables, lengths, ints: (
                    tables.at[ints[0]].set(ints[2:]),
                    lengths.at[ints[0]].set(ints[1])),
                donate_argnums=(0, 1))
            if windowed:
                # a slot's whole row of the window table (at admission and
                # release), and single entries as a row moves on: [slot, *row]
                # and [slots, page indices, page ids], a slot past the last
                # where the write carries fewer than WINDOW_WRITE entries
                self._set_win_row_fn = jax.jit(
                    lambda tables, ints: tables.at[ints[0]].set(ints[1:]),
                    donate_argnums=(0,))
                self._set_win_entries_fn = jax.jit(
                    lambda tables, ints: tables.at[ints[0], ints[1]].set(
                        ints[2], mode="drop"), donate_argnums=(0,))
                self._write_window_entries([])
            self._write_table_row(0, 0, 0)
        if stateful:
            from ray_tpu.ops.paged_attention import copy_slot_state
            # [slot, snapshot]: a slot's state into a snapshot or out of one,
            # one donated program each, compiled here as the others are
            self._state_copy = jax.jit(
                lambda cache, ints, save: copy_slot_state(
                    cache, ints[0], ints[1], save),
                donate_argnums=(0,), static_argnums=(2,))
            for save in (True, False):
                self.cache = self._state_copy(
                    self.cache, np.zeros((2,), np.int32), save)
        if self._tier is not None:
            # its two programs, compiled here and not at the first eviction
            self.cache = self._tier.warm(self.cache)
        # first token goes through the SAME sampling policy as later ones
        self._sample_first = jax.jit(
            lambda logits, key, t, p, k, want_logp=True: tuple(
                x[0] for x in sample(logits[None], key, t[None], p[None],
                                     k[None], want_logp)),
            static_argnums=(5,))

    def _idle_slots(self) -> SlotState:
        """The slot state with nothing decoding."""
        import jax.numpy as jnp
        B = self.config.max_batch_slots
        # a buffer of its own each: the state is donated whole
        return SlotState(
            last=jnp.zeros((B,), jnp.int32), active=jnp.zeros((B,), bool),
            budget=jnp.zeros((B,), jnp.int32), room=jnp.zeros((B,), jnp.int32),
            eos=jnp.full((B,), -1, jnp.int32),
            temps=jnp.zeros((B,), jnp.float32),
            top_ps=jnp.ones((B,), jnp.float32),
            top_ks=jnp.zeros((B,), jnp.int32))

    def _join(self, slot_idx: int, slot: _Slot, first) -> None:
        """`slot` decodes from the next chunk dispatched: scatter its first
        token — an int32 scalar, on the device and unseen by the host, or a
        host value (pd.py: the prompt was prefilled elsewhere) — and what
        is left of its budget and its row after that token into the
        device's slot state. Termination is the device's from here on; the
        host follows it from the tokens it reads."""
        slot.joined_max = slot.max_tokens
        self._slots = self._join_fn(
            self._slots,
            np.array([slot_idx, slot.max_tokens - 1,
                      self.config.max_seq_len - slot.prompt_len - 1,
                      -1 if slot.eos_id is None else slot.eos_id,
                      slot.top_k], np.int32),
            np.array([slot.temperature, slot.top_p], np.float32), first)
        self._active[slot_idx] = slot

    def _leave(self, slot_idx: int) -> None:
        """Take `slot_idx` out of the device's state (behind whatever chunk
        is in flight): only the host knows that a consumer walked away."""
        self._slots = self._join_fn(
            self._slots, np.array([slot_idx, 0, 0, -1, 0], np.int32),
            np.array([0.0, 1.0], np.float32), np.int32(0))

    def _chunk_len(self) -> int:
        """Adaptive decode-chunk length for THIS tick. Chunk 1 while any
        prompt is still prefilling (a queued request must not wait N device
        steps for its next chunk) and while speculation is on (the draft
        check runs per tick); otherwise min(decode_chunk, most remaining
        tokens over active slots), bucketed DOWN to a power of two so the
        jit cache holds O(log decode_chunk) variants, same idiom as the
        prefill buckets. The host's view: what it has read, less the steps
        already dispatched for a slot (`ahead`). 0 when every slot ends in
        what is in flight: nothing to dispatch. The device's own budget
        ends a slot whatever length is chosen here."""
        cfg = self.config
        rem = 0
        for slot in self._active.values():
            known = max(len(slot.generated), 1)    # first token joined, unread
            rem = max(rem, min(
                slot.max_tokens - known,
                cfg.max_seq_len - (slot.prompt_len + known)) - slot.ahead)
        if rem <= 0:
            return 0
        if cfg.decode_chunk <= 1 or self._prefill_q or cfg.speculate > 0:
            return 1
        n = min(cfg.decode_chunk, rem)
        return 1 << (n.bit_length() - 1)

    def lower_decode_chunk(self, n: Optional[int] = None):
        """The fused decode chunk the engine runs each tick, lowered
        (`jax.stages.Lowered`) at chunk length `n` (default `decode_chunk`)
        against the live params, cache and slot state — for reading its
        compiled HLO or cost analysis. Traces only: nothing is donated or
        run."""
        return self._decode_chunk.lower(
            self._run_params, self.cache, self._slots, self._sample_key, False,
            n or self.config.decode_chunk)

    def _note_sync(self, tokens: int, dt_s: float,
                   chunk: Optional[int] = None):
        """Record one host sync of the decode engine (a fused chunk or a
        speculative verify tick)."""
        st = self._decode_stats
        st["host_syncs"] += 1
        st["tokens"] += tokens
        st["chunk_s_total"] += dt_s
        st["decode_steps"] += chunk or 1     # a speculative tick is one step
        st["active_slot_syncs"] += len(self._active)
        if chunk is not None:
            st["chunk_sizes"][chunk] = st["chunk_sizes"].get(chunk, 0) + 1
        if tokens:
            self._m_tpot.observe(dt_s / tokens * 1e3, tags=self._slo_tags)
        eng_tags = {"engine": self._slo_tags["engine"]}
        cap = len(self._active) + len(self._free)
        if cap:
            self._m_occupancy.observe(len(self._active) / cap,
                                      tags=eng_tags)
        if self.page_mgr is not None and self.page_mgr.num_pages:
            self._m_kv_util.observe(
                self.page_mgr.pages_in_use / self.page_mgr.num_pages,
                tags=eng_tags)

    def _count_moe(self, routed_tokens: int, call_tokens: int,
                   calls: int = 1) -> None:
        """The expert product's rows, by arithmetic on the host: a routed
        row is one (token, expert) pair that a real token needs; a computed
        row is one the programs multiplied. `calls` forwards of
        `call_tokens` rows each ran (padding and idle slots included); the
        grouped product computes top_k rows a token, the dropless one-hot
        dispatch E x C with C = ceil(capacity_factor x top_k x S / E)."""
        layers = self._moe_layers
        if not layers:
            return
        mc = self.model_cfg
        E, K = mc.n_experts, mc.moe_top_k
        per_call = (call_tokens * K if self._moe_grouped else
                    E * max(1, math.ceil(
                        mc.capacity_factor * K * call_tokens / E)))
        self._moe_stats["routed_rows"] += routed_tokens * K * layers
        # (a bank with a share of the experts counts what it multiplied on
        # the device: stats() reads it)
        self._moe_stats["computed_rows"] += calls * per_call * layers

    def _count_sparse(self, first_context: int, steps: int) -> None:
        """`steps` decode steps of one row whose first query sees
        `first_context` keys (its own included) and each next one more: how
        many keys the contexts held, how many the selection kept (all of
        them up to `index_topk`), how many of the steps kept all, and how
        many keys the scoring kernel's walk covered (a context rounded up to
        the walk's block: it follows the rows while scored / context keys
        stays near 1, and would be the table's width over the mean context
        if it did not)."""
        topk = self.model_cfg.index_topk
        if not topk or steps <= 0:
            return
        last = first_context + steps - 1
        block = self._index_block
        dense = max(0, min(last, topk) - first_context + 1)
        sp = self._sparse_stats
        sp["decode_rows"] += steps
        sp["dense_rows"] += dense
        sp["context_keys"] += steps * (first_context + last) // 2
        sp["selected_keys"] += (dense * (2 * first_context + dense - 1) // 2
                                + (steps - dense) * topk)
        sp["scored_keys"] += block * sum(
            -(-context // block) for context in range(first_context, last + 1))

    def reconfigure(self, user_config: Optional[Dict[str, Any]]):
        """Serve `user_config` hook (replica.py calls this at deployment
        and on in-place updates): adjust engine knobs that need neither a
        param reload nor a cache rebuild. `decode_chunk` is the first such
        knob — the jit cache keys on the chunk length, so a new value just
        compiles its variant on first use."""
        if not user_config:
            return
        if "decode_chunk" in user_config:
            n = int(user_config["decode_chunk"])
            if n < 1:
                raise ValueError(f"decode_chunk must be >= 1, got {n}")
            self.config.decode_chunk = n

    def close(self) -> None:
        """Give back what the engine keeps outside the process (the shared
        memory segments and spill files of demoted KV pages), after what is
        on its way there. For whoever tears the server down; closing twice
        is fine, and `stats()` still answers afterwards."""
        if self._tier is not None:
            self._tier.close()

    @property
    def _kv_stash(self):
        """The tier's stash (None without one): read by perfbench."""
        return self._tier.stash if self._tier is not None else None

    def _bucket(self, n: int) -> int:
        """Pad prompt lengths to power-of-two buckets: few compiled prefill
        variants instead of one per length. Clamped to the cache row size —
        a larger padded write would violate KVCache's capacity invariant."""
        b = 16
        while b < n:
            b *= 2
        return min(b, self.config.max_seq_len)

    # -- request admission ---------------------------------------------------
    def _make_slot(self, prompt_len: int, max_tokens: int,
                   eos_id: Optional[int], stream: bool, temperature,
                   top_p, top_k, logprobs: bool,
                   prompt_ids: Optional[List[int]] = None) -> _Slot:
        """Single site for per-request state + sampling-default fallbacks —
        shared with the PD decode path (pd.py) so a new sampling knob can't
        silently diverge between colocated and disaggregated admission."""
        cfg = self.config
        return _Slot(request_id=self._req_counter, prompt_len=prompt_len,
                     max_tokens=max_tokens, generated=[],
                     done_event=asyncio.Event(),
                     stream_queue=asyncio.Queue() if stream else None,
                     eos_id=eos_id,
                     temperature=(cfg.temperature if temperature is None
                                  else temperature),
                     top_p=cfg.top_p if top_p is None else top_p,
                     top_k=cfg.top_k if top_k is None else top_k,
                     want_logprobs=logprobs, prompt_ids=prompt_ids or [])

    async def _admit(self, prompt_ids: List[int], max_tokens: int,
                     eos_id: Optional[int], stream: bool,
                     temperature: Optional[float] = None,
                     top_p: Optional[float] = None,
                     top_k: Optional[int] = None,
                     logprobs: bool = False) -> _Slot:
        P = len(prompt_ids)
        t_admit = time.monotonic()
        # feasibility (max_seq_len, page-pool capacity) raises in _reserve
        slot_idx, cached = await self._reserve(prompt_ids, P + max_tokens)
        slot = self._make_slot(P, max_tokens, eos_id, stream, temperature,
                               top_p, top_k, logprobs,
                               # the retained copy feeds prompt-lookup
                               # drafting only — don't hold every prompt
                               # alive for the common speculate=0 config
                               prompt_ids=(list(prompt_ids)
                                           if self.config.speculate > 0
                                           else None))
        # the engine feeds the prompt through in chunks, interleaved with
        # decode ticks for already-active slots (chunked prefill). A cached
        # prefix starts the job past the shared pages — their KV is already
        # resident (prefix cache: the TTFT win is skipping this compute)
        self._prefill_q.append(_PrefillJob(
            slot_idx=slot_idx, slot=slot,
            prompt=np.asarray(list(prompt_ids), np.int32), pos=cached))
        self._ensure_tick_loop()
        await slot.first_token.wait()
        if slot.error is not None:
            raise RuntimeError("prefill failed") from slot.error
        # TTFT = admission (queueing for a slot/pages included) → first
        # token available; both generate and generate_stream come through
        # here, so the histogram covers every request
        self._m_ttft.observe(time.monotonic() - t_admit, tags=self._slo_tags)
        return slot

    async def _reserve(self, prompt_ids, total_len: int,
                       use_prefix: bool = True):
        """Wait for a free slot AND enough free pages (vLLM-style admission:
        reserve the full request up front, so decode never OOMs), then
        allocate. Event-driven: _release_slot wakes every waiter; re-check.
        Returns (slot_idx, cached_prefix_tokens)."""
        t_in = time.perf_counter()
        if total_len > self.config.max_seq_len:
            raise ValueError(
                f"request needs {total_len} tokens but max_seq_len is "
                f"{self.config.max_seq_len}")
        mgr = self.page_mgr
        if mgr is not None:
            need = -(-total_len // mgr.page_size)
            if need > min(mgr.num_pages - 1, mgr.max_pages_per_seq):
                # infeasible FOREVER — raise rather than wait on capacity
                # that can never exist (r5 review: PD callers hung here)
                raise ValueError(
                    f"request needs {need} KV pages but the pool can never "
                    f"hold more than "
                    f"{min(mgr.num_pages - 1, mgr.max_pages_per_seq)} "
                    f"per sequence (num_pages={mgr.num_pages}, "
                    f"page_size={mgr.page_size})")
            if mgr.win_num_pages and (
                    min(need, mgr.window_budget) > mgr.win_num_pages - 1):
                raise ValueError(
                    f"request needs {min(need, mgr.window_budget)} window "
                    f"pages but that pool holds {mgr.win_num_pages - 1}")

        def fits():
            if mgr is None:
                return True
            # the wait condition must mirror the allocator it gates:
            # prefix-crediting admission for allocate_prefix, the full page
            # bill for plain allocate (r5 review: a prefix-credited wait
            # followed by a full-bill allocate raised MemoryError mid-flight)
            if use_prefix and self.config.prefix_cache:
                return mgr.can_fit_prompt(list(prompt_ids), total_len)
            return mgr.can_fit(total_len)

        while not self._free or not fits():
            self._capacity_event.clear()
            await self._capacity_event.wait()
        # the wait straddles awaits and other tasks' phases, so it is a
        # counter and not an annotation
        st = self._decode_stats
        waited = time.perf_counter() - t_in
        st["admitted"] += 1
        st["slot_wait_s"] += waited
        st["slot_wait_max_s"] = max(st["slot_wait_max_s"], waited)
        slot_idx = self._free.pop()
        self._req_counter += 1
        cached = 0
        try:
            with phase(self._phases, "admit_allocate"):   # no await inside
                if mgr is not None:
                    if use_prefix and self.config.prefix_cache:
                        row, cached = mgr.allocate_prefix(
                            slot_idx, list(prompt_ids), total_len)
                        self.cache = self._tier.flush_restores(self.cache)
                    else:
                        row = mgr.allocate(slot_idx, total_len)
                    # lengths[slot] must point PAST the shared prefix before
                    # the next decode tick: write_layer_tokens writes every
                    # row at its length each tick, and a 0 here would land
                    # garbage KV at position 0 of a SHARED page — corrupting
                    # the cached prefix for every borrower. At `cached` the
                    # stray write hits the first FRESH page and prefill chunk
                    # 1 overwrites it (same contract as the uncached pos-0
                    # write).
                    self._write_table_row(slot_idx, row, cached)
                    if self._stateful and cached:
                        with phase(self._phases, "state_restore"):
                            self._copy_state(slot_idx,
                                             mgr.resume_snapshot(slot_idx),
                                             save=False)
        except BaseException:
            self._release_slot(slot_idx)
            raise
        return slot_idx, cached

    def _prefill_chunk(self, job: _PrefillJob):
        """Run ONE chunk of `job`'s prompt; returns final-chunk logits or
        None. Chunk shapes come from a fixed bucket set, so XLA compiles a
        handful of prefill programs total."""
        P = len(job.prompt)
        start = job.pos
        n = min(self.config.prefill_chunk, P - start)
        # a model with state stops at the prompt's last page boundary (what
        # a later prompt can match of this one ends there), saves the state
        # and runs the tail, at most a page, as the final chunk; and before
        # that where the prompt leaves the tree past the snapshot it resumed
        # from, so that the next prompt that shares those pages finds a state
        # at their end (radix_cache.py)
        ps = self.config.page_size
        boundary = (P - 1) // ps * ps if self._stateful else 0
        branch = (self.page_mgr.branch_stop(job.slot_idx) * ps
                  if self._stateful else 0)
        for stop in (branch, boundary):
            if start < stop:
                n = min(n, stop - start)
                break
        final = start + n >= P
        # clamp the padded bucket to the row capacity: a write spanning past
        # max_seq_len would be CLAMPED by dynamic_update_slice and land
        # shifted over earlier prompt KV (llama.py documents the clamp)
        bucket = (min(self._bucket(n), self.config.max_seq_len - start)
                  if final or n < self.config.prefill_chunk
                  else self.config.prefill_chunk)
        if self._windowed:
            self._window_advance([(job.slot_idx, start, start + n)])
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = job.prompt[start:start + n]
        # host values go up with the call itself
        args = (self._run_params, self.cache, padded, job.slot_idx,
                np.int32(start), np.int32(start + n))
        if self.config.paged:
            # start==0 → fresh row's first chunk: exact with chunk-local
            # attention (static flag, no full-row page gather on the hot
            # cold-prompt path)
            self.cache, last_logits = self._prefill(*args, start == 0)
        else:
            self.cache, last_logits = self._prefill(*args)
        job.pos += n
        if n and job.pos in (branch, boundary):
            sid = self.page_mgr.reserve_snapshot(
                job.slot_idx, job.pos // ps, branch=job.pos != boundary)
            if sid is not None:
                with phase(self._phases, "state_save"):
                    self._copy_state(job.slot_idx, sid, save=True)
        st = self._decode_stats
        st["prefill_chunks"] += 1
        st["prefill_tokens"] += n
        st["prefill_padded_tokens"] += bucket
        if start:
            st["continuation_chunks"] += 1
            st["continuation_reach_keys"] += start + n
            st["continuation_query_keys"] += n * start + n * (n + 1) // 2
            if self._windowed:
                # query j of the chunk sees min(start + j + 1, window) keys
                w = self.model_cfg.sliding_window
                ramp = min(n, max(0, w - start - 1))    # queries still short
                seen = ramp * start + ramp * (ramp + 1) // 2 + (n - ramp) * w
                self._window_stats["window_query_keys"] += seen
                self._window_recent["recent_continuations"].append(
                    (time.monotonic(), seen, n * start + n * (n + 1) // 2))
        self._count_moe(n, bucket)
        return last_logits if final else None

    @staticmethod
    def _lookup_draft(ctx: List[int], k: int, n: int) -> List[int]:
        """Prompt-lookup draft: the continuation of the MOST RECENT earlier
        occurrence of the context's final n-gram ([] when none). REFERENCE
        implementation (unit-tested): the engine itself keeps an
        incremental per-slot {n-gram -> continuation start} index with the
        same most-recent-match semantics, O(1) per tick."""
        L = len(ctx)
        if L <= n:
            return []
        tail = ctx[-n:]
        for i in range(L - n - 1, -1, -1):
            if ctx[i:i + n] == tail:
                return ctx[i + n:i + n + k]
        return []

    def _spec_drafts(self) -> Optional[Dict[int, List[int]]]:
        """Decide whether THIS tick runs the speculative step. Returns
        {slot: draft} when it should, None for a plain decode tick —
        speculation needs K+1 free cache positions on every row it
        touches (the verify forward writes K+1 entries unconditionally;
        a clamped write would silently overwrite valid KV — the KVCache
        capacity invariant), including rows still MID-PREFILL, and at
        least one greedy slot with a real n-gram hit (a tick with no
        usable draft would pay the (K+1)-position forward for nothing)."""
        cfg = self.config
        K = cfg.speculate
        n = cfg.spec_ngram
        if self._spec is None or not self._active:
            return None
        for job in self._prefill_q:
            # a prefilling row's committed length is job.pos; the spec
            # write lands K+1 entries there too
            if job.pos + K + 1 > cfg.max_seq_len:
                return None
        drafts: Dict[int, List[int]] = {}
        for i, slot in self._active.items():
            if slot.prompt_len + len(slot.generated) + K + 1 > cfg.max_seq_len:
                return None
            if slot.temperature > 0:
                continue
            ctx = slot.ctx
            if len(ctx) != slot.prompt_len + len(slot.generated):
                # first spec tick for this slot (or a non-emit_one append
                # happened, e.g. the prefill first-token): (re)build the
                # incremental index once; emit_one keeps it current after
                ctx = slot.ctx = slot.prompt_ids + slot.generated
                slot.spec_index = {
                    tuple(ctx[e - n:e]): e for e in range(n, len(ctx))}
            pos = slot.spec_index.get(tuple(ctx[-n:]))
            if pos is not None:
                drafts[i] = ctx[pos:pos + K]
        return drafts or None

    def _ensure_tick_loop(self):
        if self._tick_task is None or self._tick_task.done():
            self._tick_task = asyncio.get_running_loop().create_task(
                self._tick_loop())

    def _stall_facts(self) -> Dict[str, Any]:
        """The engine as the watchdog's thread finds it while a read is
        stalled: plain reads of the loop's own state, no call into jax."""
        reading = self._reading
        return {"inflight": len(self._inflight),
                "reading_seq": reading.seq if reading else None,
                "reading_steps": reading.n if reading else None,
                "first_pending": len(self._first_pending),
                "active": len(self._active),
                "queued_prompts": len(self._prefill_q),
                "staged_bytes": (self._tier.staged_bytes
                                 if self._tier is not None else 0),
                "ticks": self._decode_stats["ticks"]}

    def _file_stall(self, record: Dict[str, Any]) -> None:
        """On the watchdog's thread: keep the record and log it, once."""
        self._stalls.append(record)
        logger.warning("%s", json.dumps(record))

    async def _tick_loop(self):
        watch = StallWatch(self._phases, self._stall_facts,
                           self._file_stall).start()
        try:
            await self._tick_loop_inner()
        except BaseException as e:  # noqa: BLE001 - fail every waiter loudly
            for job in list(self._prefill_q):
                job.slot.error = e
                job.slot.first_token.set()
                job.slot.done_event.set()
                if job.slot.stream_queue is not None:
                    job.slot.stream_queue.put_nowait(None)
                self._release_slot(job.slot_idx)
            self._prefill_q.clear()
            for i, slot in list(self._active.items()):
                slot.error = e
                slot.first_token.set()
                slot.done_event.set()
                if slot.stream_queue is not None:
                    slot.stream_queue.put_nowait(None)
                self._release_slot(i)
            self._active.clear()
            # nothing in flight is read any more, and no slot decodes
            self._inflight.clear()
            self._first_pending.clear()
            self._slots = self._idle_slots()
            raise
        finally:
            watch.stop()

    def _copy_state(self, slot_idx: int, snapshot: int, save: bool) -> None:
        """A slot's recurrent state into snapshot `snapshot`, or out of it,
        behind whatever is in flight (the manager may hand the id to another
        request at once: programs run in the order they were dispatched)."""
        self.cache = self._state_copy(
            self.cache, np.array([slot_idx, snapshot], np.int32), save)
        self._state_stats["snapshot_copies" if save
                          else "restore_copies"] += 1

    def _write_table_row(self, slot_idx: int, row, length: int) -> None:
        """The slot's block-table row and its length on the newest cache, in
        one donated program behind whatever is in flight. (Eager `.at[].set`
        on arrays a queued chunk has yet to produce held the loop 12-24 ms
        a call on the v5e: my chip runs, PR 29.)"""
        ints = np.empty((2 + self.cache.block_tables.shape[1],), np.int32)
        ints[0], ints[1], ints[2:] = slot_idx, length, row
        tables, lengths = self._set_row_fn(
            self.cache.block_tables, self.cache.lengths, ints)
        self.cache = self.cache.replace(block_tables=tables, lengths=lengths)
        if self._windowed:
            ints = np.empty((1 + self.cache.win_tables.shape[1],), np.int32)
            ints[0], ints[1:] = slot_idx, self.page_mgr.win_table_row(slot_idx)
            self.cache = self.cache.replace(win_tables=self._set_win_row_fn(
                self.cache.win_tables, ints))

    def _write_window_entries(self, entries) -> None:
        """New (slot, page index, page id) entries of the window table onto
        the device, behind whatever is in flight."""
        B = self.config.max_batch_slots
        for i in range(0, max(len(entries), 1), WINDOW_WRITE):
            part = np.asarray(entries[i:i + WINDOW_WRITE], np.int32)
            ints = np.zeros((3, WINDOW_WRITE), np.int32)
            ints[0] = B                       # past the last slot: dropped
            ints[:, :len(part)] = part.reshape(-1, 3).T
            self.cache = self.cache.replace(win_tables=self._set_win_entries_fn(
                self.cache.win_tables, ints))
            self._window_stats["window_table_writes"] += 1

    def _window_advance(self, rows) -> None:
        """Before a program is dispatched, for each (slot, t_min, upto) of
        `rows`: the slot's first query in it sits at `t_min` or later and it
        writes positions below `upto`. The window pages that no later query
        of the slot sees go back, the ones it writes are taken, and their
        table entries are sent in one write."""
        with phase(self._phases, "window_release"):
            new = [(slot, i, pid) for slot, t_min, upto in rows
                   for i, pid in self.page_mgr.window_advance(
                       slot, t_min, upto)]
            if new:
                self._write_window_entries(new)

    def _release_slot(self, i: int):
        """Return slot i to the pool; paged mode also frees its pages and
        zeroes its table row so inactive-slot decode writes land on the
        reserved placeholder page, never on another request's pages."""
        if self.page_mgr is not None:
            self.page_mgr.free(i)
            self._write_table_row(i, 0, 0)
        self._free.append(i)
        self._capacity_event.set()  # wake admission waiters

    async def _tick_loop_inner(self):
        """The continuous-batching engine: each iteration runs ONE fused
        decode chunk (1.._chunk_len() on-device steps) for every active
        slot AND (at most) one prefill chunk of the oldest queued prompt —
        a long prompt adds one chunk of latency per tick instead of
        stalling every stream for its full prefill (chunked prefill; ref:
        the reference's PD-disaggregation serving pattern). While prompts
        are queued the decode chunk stays at 1, so admission latency never
        grows with decode_chunk; streaming slots' queues are flushed once
        per chunk, in token order.

        The loop runs one program AHEAD of what it reads, so that it never
        blocks on the device with nothing queued behind what it waits for.
        What a chunk starts from is on the device (`self._slots`, carried
        from chunk to chunk beside `self.cache`), so tick k dispatches
        chunk k+1 from it and only then reads chunk k, whose copy to the
        host was started at its dispatch; it emits k's tokens, releases
        what finished (a slot that ended in k ran frozen through k+1, and
        its table row is zeroed behind k+1), dispatches one prefill chunk
        and yields. A prompt's first token is sampled and joined to its
        slot on the device (`_first_token`); the host reads it just before
        it reads the first chunk dispatched after the join (where it waits
        for the prefill's end it does so with that chunk queued behind),
        or at the end of a tick if it finds it ready. The order
        of programs on the device is what it always was: one prefill chunk
        between two decode chunks, a joined slot decodes from the next
        chunk dispatched.

        A speculating engine (`speculate > 0`) drafts from the tokens of
        the tick before, so it reads what it dispatched at once, first
        tokens too: same calls, nothing in flight across a tick."""
        ahead = self.config.speculate == 0
        ph, st = self._phases, self._decode_stats
        while self._active or self._prefill_q or self._inflight:
            t_tick = time.perf_counter()
            dispatched = False
            if self._active:
                with phase(ph, "decode_build"):
                    self._finish_shrunk()
                    drafts = self._spec_drafts()
                    n = self._chunk_len()
                if n:
                    with phase(ph, "decode_dispatch"):
                        self._dispatch_chunk(drafts, n)
                    dispatched = True
            # all but the chunk just dispatched; all of them when the engine
            # does not run ahead or has nothing more to dispatch
            keep = 1 if ahead and dispatched else 0
            while len(self._inflight) > keep:
                self._read_first_tokens(before=self._inflight[0].seq)
                with phase(ph, "decode_sync"):
                    # host blocked, device busy: the one sync of the tick,
                    # with the next chunk already queued behind it
                    chunk, out = self._read_chunk()
                with phase(ph, "decode_emit"):
                    self._emit_chunk(chunk, *out)
            if not self._inflight:
                self._read_first_tokens()
            if self._prefill_q:
                job = self._prefill_q[0]
                try:
                    with phase(ph, "prefill_dispatch"):
                        last_logits = self._prefill_chunk(job)
                except BaseException as e:  # noqa: BLE001 - fail the request
                    self._prefill_q.popleft()
                    job.slot.error = e
                    job.slot.first_token.set()
                    job.slot.done_event.set()
                    if job.slot.stream_queue is not None:
                        job.slot.stream_queue.put_nowait(None)
                    self._release_slot(job.slot_idx)
                else:
                    if last_logits is not None:  # prompt fully prefilled
                        with phase(ph, "prefill_first_token"):
                            self._first_token(job, last_logits)
                        if not ahead:
                            self._read_first_tokens()
            self._read_first_tokens(ready_only=True)
            with phase(ph, "yield"):
                # let admits interleave between ticks: their phases
                # (admit_allocate, evict, demote, restore) nest in this one,
                # beside the stream flushes and the replica's calls
                await asyncio.sleep(0)
            st["loop_s"] += time.perf_counter() - t_tick
            st["ticks"] += 1

    def _emit_one(self, slot: _Slot, tok: int, lp: float) -> bool:
        """Append one token to `slot`; True when the slot is done."""
        slot.generated.append(tok)
        if slot.ctx:   # incremental prompt-lookup index maintenance
            ctx, n_gram = slot.ctx, self.config.spec_ngram
            ctx.append(tok)
            L = len(ctx)
            if L > n_gram:
                # the n-gram ending at L-2 gained a continuation (L-1)
                slot.spec_index[tuple(ctx[L - 1 - n_gram:L - 1])] = L - 1
        if slot.want_logprobs:
            slot.logprobs.append(lp)
        if slot.stream_queue is not None:
            slot.stream_queue.put_nowait(tok)
        hit_eos = slot.eos_id is not None and tok == slot.eos_id
        total = slot.prompt_len + len(slot.generated)
        return (len(slot.generated) >= slot.max_tokens or hit_eos
                or total >= self.config.max_seq_len)

    def _finish(self, i: int, slot: _Slot) -> None:
        """`slot` has its last token: wake its waiters and give slot `i`
        back. The device ended it at the same token from the budget it was
        joined with; a `max_tokens` the host shrank since is the one end the
        device cannot know of, so the slot is taken out of its state."""
        if self._active.get(i) is not slot:
            return
        del self._active[i]
        slot.done_event.set()
        if slot.stream_queue is not None:
            slot.stream_queue.put_nowait(None)
        if slot.max_tokens != slot.joined_max:
            self._leave(i)
        self._release_slot(i)

    def _finish_shrunk(self) -> None:
        """A consumer that walked away shrank its slot's `max_tokens` to
        what it had: such a slot is done now, with no token of it to wait
        for unless steps of it are in flight."""
        shrunk = [(i, slot) for i, slot in self._active.items()
                  if slot.generated and not slot.ahead
                  and len(slot.generated) >= slot.max_tokens]
        for i, slot in shrunk:
            self._finish(i, slot)

    def _dispatch_chunk(self, drafts, n: int) -> None:
        """Dispatch the next decode chunk of `n` steps (or, with `drafts`,
        one speculative verify) from the carried slot state and start the
        copy of its results to the host; nothing is waited for."""
        import jax

        st = self._decode_stats
        t0 = time.perf_counter()
        slots = list(self._active.items())
        any_logp = any(s.want_logprobs for _, s in slots)
        if self._windowed:
            # of the steps dispatched for a slot the host has read
            # len(generated) - 1 (the first token came from the prefill):
            # the next query it has not seen the result of sits there, and
            # this chunk writes no further than `ahead + n` past it
            self._window_advance([
                (i, s.prompt_len + max(len(s.generated), 1) - 1,
                 s.prompt_len + len(s.generated) + s.ahead + n + 1)
                for i, s in slots])
        if drafts is not None:
            # speculative tick: one [B, K+1] verify forward
            n = None
            padded = np.zeros((self.config.max_batch_slots,
                               self.config.speculate), np.int32)
            for i, d in drafts.items():
                padded[i, :len(d)] = d
            self._sample_key, sub = jax.random.split(self._sample_key)
            self.cache, self._slots, *out = self._spec(
                self._run_params, self.cache, self._slots, padded, sub,
                any_logp)
        else:
            # fused multi-token decode: n steps on device. The chunk fn
            # splits the sample key once per step and returns the carried
            # key — the same key stream the per-step loop consumed, so
            # chunking never changes sampled outputs.
            (self.cache, self._slots, toks, n_valid, logp,
             self._sample_key, *touched) = self._decode_chunk(
                self._run_params, self.cache, self._slots, self._sample_key,
                any_logp, n)
            out = [toks, n_valid, logp, *touched]
        for x in out:
            x.copy_to_host_async()
        if self._inflight:
            st["run_ahead_chunks"] += 1
        for _, slot in slots:
            slot.ahead += n or 1
        self._n_dispatched += 1
        self._inflight.append(_Chunk(
            seq=self._n_dispatched, n=n, slots=slots, out=tuple(out),
            t_dispatch=t0, drafts=drafts))

    def _read_chunk(self):
        """Block on the oldest chunk in flight; returns it and its results
        as host arrays."""
        import jax

        chunk = self._reading = self._inflight.popleft()
        t0 = time.perf_counter()
        out = [np.asarray(x) for x in jax.device_get(chunk.out)]
        if not self._inflight:
            self._decode_stats["read_wait_s"] += time.perf_counter() - t0
        self._reading = None
        return chunk, out

    def _emit_chunk(self, chunk: _Chunk, toks, n_valid, logp, *touched):
        """Hand the tokens of a chunk just read to their requests, finish
        what ended in it, and record the sync: everything that describes a
        chunk is stamped here, at its read."""
        B, K = self.config.max_batch_slots, self.config.speculate
        n, drafts = chunk.n, chunk.drafts
        sp = self._spec_stats
        if drafts is not None:
            sp["spec_ticks"] += 1
            sp["drafted"] += sum(len(d) for d in drafts.values())
        else:
            sp["decode_ticks"] += 1
        emitted = 0
        finished = []
        seen_before = (self._window_stats["decode_window_keys"],
                       self._window_stats["decode_full_keys"])
        for i, slot in chunk.slots:
            slot.ahead -= n or 1
            if slot.done_event.is_set():    # the host ended it meanwhile
                continue
            cnt = int(n_valid[i])
            self._count_sparse(slot.prompt_len + len(slot.generated), cnt)
            if self._windowed and cnt > 0:
                # step j's query sees first + j keys, its own included
                first = slot.prompt_len + len(slot.generated)
                w = self.model_cfg.sliding_window
                short = max(0, min(cnt, w - first))     # steps under the window
                ws = self._window_stats
                ws["decode_full_keys"] += cnt * first + cnt * (cnt - 1) // 2
                ws["decode_window_keys"] += (
                    short * first + short * (short - 1) // 2
                    + (cnt - short) * w)
            if drafts is not None and i in drafts:
                # clip: a short draft's zero-padding can "accidentally"
                # match argmax (still exact output) but must not count as
                # acceptance
                sp["accepted"] += min(cnt - 1, len(drafts[i]))
            for j in range(cnt):
                emitted += 1
                if self._emit_one(slot, int(toks[i, j]), float(logp[i, j])):
                    finished.append((i, slot))
                    break
        # what a stream sees: the time from one chunk's read to the next
        now = time.perf_counter()
        self._note_sync(emitted, now - max(self._t_read, chunk.t_dispatch),
                        chunk=n)
        self._t_read = now
        if self._windowed:
            self._window_recent["recent_decode_syncs"].append((
                time.monotonic(),
                self._window_stats["decode_window_keys"] - seen_before[0],
                self._window_stats["decode_full_keys"] - seen_before[1]))
        if n is None:    # one verify forward of K + 1 positions
            self._count_moe(emitted, B * (K + 1))
        else:
            self._count_moe(emitted, B, calls=n)
        if touched:   # every step of the chunk ran every layer
            calls = n * self._moe_layers
            seen = int(touched[0].sum())
            self._moe_stats["decode_layer_calls"] += calls
            self._moe_stats["decode_experts_touched"] += seen
            self._moe_recent.append((time.monotonic(), calls, seen))
        for i, slot in finished:
            self._finish(i, slot)

    def _first_token(self, job: _PrefillJob, last_logits):
        """The prompt is fully prefilled: publish its pages, sample the
        first token with the policy of every later one (the key a chunk
        handed back is split for it, then goes into the next chunk) and
        join it to its slot ON THE DEVICE: token, budget and row's room go
        into the carried slot state as device values, so the slot decodes
        from the next chunk dispatched and nothing is read here. The host
        reads the token later (`_read_first_tokens`), before any token of
        the chunks that follow."""
        import jax

        self._prefill_q.popleft()
        if self.page_mgr is not None and self.config.prefix_cache:
            # publish this prompt's full pages for reuse
            self.page_mgr.register_prefix(job.slot_idx, job.prompt.tolist())
        slot = job.slot
        self._sample_key, sub = jax.random.split(self._sample_key)
        first, flogp = self._sample_first(
            last_logits, sub, np.float32(slot.temperature),
            np.float32(slot.top_p), np.int32(slot.top_k), slot.want_logprobs)
        self._join(job.slot_idx, slot, first)
        first.copy_to_host_async()
        flogp.copy_to_host_async()
        self._first_pending.append(
            (self._n_dispatched, job.slot_idx, slot, first, flogp))
        self._decode_stats["joined_on_device"] += 1

    def _read_first_tokens(self, before: Optional[int] = None,
                           ready_only: bool = False) -> None:
        """Hand the first tokens joined on the device to their requests,
        oldest first: those joined before chunk `before` was dispatched
        (that chunk holds their slots' next tokens; None: all of them), or
        with `ready_only` those whose program has ended, without waiting.
        A first token that ends its request finishes the slot here; the
        device left it inactive at the join."""
        import jax

        pending = self._first_pending
        while pending and (before is None or pending[0][0] < before):
            _, i, slot, first, flogp = pending[0]
            if ready_only and not first.is_ready():
                return
            pending.popleft()
            # a further stretch of the entry `_first_token` counted
            with phase(self._phases, "prefill_first_token", entries=0):
                t0 = time.perf_counter()
                first, flogp = jax.device_get((first, flogp))
                if not self._inflight:
                    self._decode_stats["read_wait_s"] += (
                        time.perf_counter() - t0)
                done = self._emit_one(slot, int(first), float(flogp))
                slot.first_token.set()
                if done:
                    self._finish(i, slot)

    # -- public api ----------------------------------------------------------
    async def generate(self, prompt_ids: List[int], max_tokens: int = 32,
                       eos_id: Optional[int] = None,
                       temperature: Optional[float] = None,
                       top_p: Optional[float] = None,
                       top_k: Optional[int] = None,
                       logprobs: bool = False) -> Dict[str, Any]:
        t0 = time.perf_counter()
        slot = await self._admit(list(prompt_ids), max_tokens, eos_id, False,
                                 temperature=temperature, top_p=top_p,
                                 top_k=top_k, logprobs=logprobs)
        ttft = time.perf_counter() - t0
        await slot.done_event.wait()
        if slot.error is not None:
            raise RuntimeError("decode engine failed") from slot.error
        toks = slot.generated[:max_tokens]
        if eos_id is not None and eos_id in toks:
            toks = toks[:toks.index(eos_id)]
        out = {"tokens": toks, "ttft_s": ttft,
               "total_s": time.perf_counter() - t0}
        if logprobs:
            out["logprobs"] = slot.logprobs[:len(toks)]
        return out

    async def generate_stream(self, prompt_ids: List[int],
                              max_tokens: int = 32,
                              eos_id: Optional[int] = None,
                              temperature: Optional[float] = None,
                              top_p: Optional[float] = None,
                              top_k: Optional[int] = None):
        slot = await self._admit(list(prompt_ids), max_tokens, eos_id, True,
                                 temperature=temperature, top_p=top_p,
                                 top_k=top_k)
        emitted = 0
        try:
            while emitted < max_tokens:
                tok = await slot.stream_queue.get()
                if tok is None or (eos_id is not None and tok == eos_id):
                    break
                emitted += 1
                yield tok
            if slot.error is not None:
                raise RuntimeError("decode engine failed") from slot.error
        finally:
            # consumer walked away early (stop string matched, client
            # disconnected): shrink the budget so the tick loop finishes
            # and releases this slot next tick instead of decoding — and
            # holding batch slot + KV pages — all the way to max_tokens
            slot.max_tokens = min(slot.max_tokens, len(slot.generated))

    async def embed(self, prompt_ids: List[int]) -> List[float]:
        """Mean-pooled final-hidden-state embedding of the prompt
        (reference: /v1/embeddings on the LLM ingress). Pads to the same
        power-of-two buckets as prefill — one compile per bucket; causal
        attention means pad rows past the prompt cannot leak into the
        pooled rows."""
        import jax
        import jax.numpy as jnp

        P = len(prompt_ids)
        if P == 0:
            raise ValueError("cannot embed an empty prompt")
        if P > self.config.max_seq_len:
            raise ValueError(
                f"prompt has {P} tokens but max_seq_len is "
                f"{self.config.max_seq_len}")
        b = self._bucket(P)
        if not hasattr(self, "_embed_jit"):
            def embed_fn(params, tokens, length):
                hidden, _ = self.model.apply(params, tokens,
                                             return_hidden=True)
                mask = (jnp.arange(tokens.shape[1]) <
                        length)[None, :, None].astype(hidden.dtype)
                pooled = (hidden * mask).sum(axis=1) / jnp.maximum(
                    length, 1).astype(hidden.dtype)
                return pooled[0].astype(jnp.float32)
            self._embed_jit = jax.jit(embed_fn)
        tokens = np.zeros((1, b), np.int32)
        tokens[0, :P] = prompt_ids
        vec = self._embed_jit(self.params, jnp.asarray(tokens), jnp.int32(P))
        return [float(x) for x in np.asarray(vec)]

    def prefix_digest(self, max_bytes: int = None) -> Optional[Dict]:
        """Hot-prefix digest for the affinity router (ISSUE 20): the radix
        tree's resident-or-restorable chains, hashed + hit-counted, packed
        <= 4 KiB. None for engines without a prefix cache (nothing to
        advertise). The serve Replica wrapper piggybacks this on its
        stats() frame."""
        if self.page_mgr is not None and self.config.prefix_cache:
            return self.page_mgr.prefix_digest(max_bytes)
        return None

    def slo_snapshot(self) -> Dict[str, Any]:
        """Windowed SLO read for the fleet autoscaler: TTFT/TPOT quantiles
        and batch occupancy over the observations since the LAST call (the
        controller polls once per evaluation interval, so this is the
        per-interval signal — a cumulative p99 would mask fresh breaches)."""
        from ray_tpu.util import metrics as _metrics
        ttft = _metrics.histogram_window("serve_ttft_s",
                                         self._slo_window_state)
        tpot = _metrics.histogram_window("serve_tpot_ms",
                                         self._slo_window_state)
        occ = _metrics.histogram_window("serve_batch_occupancy",
                                        self._slo_window_state)
        return {
            "ttft_p99_s": ttft["p99"] if ttft else None,
            "ttft_count": ttft["count"] if ttft else 0,
            "tpot_p99_ms": tpot["p99"] if tpot else None,
            "occupancy_mean": occ["mean"] if occ else None,
            "active": len(self._active),
            "free_slots": len(self._free),
        }

    def stats(self) -> Dict[str, Any]:
        s = {"active": len(self._active), "free_slots": len(self._free),
             "requests": self._req_counter}
        tier = self._tier
        if tier is not None:
            tier.reap()
        st = self._decode_stats
        s["decode"] = {
            "decode_chunk": self.config.decode_chunk,
            "host_syncs": st["host_syncs"],
            "tokens": st["tokens"],
            "tokens_per_sync": round(
                st["tokens"] / max(st["host_syncs"], 1), 2),
            "host_syncs_per_token": round(
                st["host_syncs"] / max(st["tokens"], 1), 5),
            "chunk_s_total": round(st["chunk_s_total"], 4),
            "chunk_ms_avg": round(
                st["chunk_s_total"] / max(st["host_syncs"], 1) * 1e3, 3),
            "chunk_sizes": dict(st["chunk_sizes"]),
            # the loop's own account of its time (LOOP_PHASES sum to loop_s,
            # NESTED_PHASES are their children) and of its work
            "loop_s": st["loop_s"], "ticks": st["ticks"],
            "rotary_split_projections": self._rotary_split,
            "phase_s": dict(self._phases.seconds),
            "phase_n": dict(self._phases.counts),
            # watched reads that outlasted their work (WATCHED_PHASES)
            "stall_s": dict(self._phases.stall_seconds),
            "stall_n": dict(self._phases.stall_counts),
            "stall_max_s": self._phases.stall_max_s,
            **{k: st[k] for k in (
                "decode_steps", "active_slot_syncs", "prefill_chunks",
                "prefill_tokens", "prefill_padded_tokens",
                "continuation_chunks", "continuation_reach_keys",
                "continuation_query_keys", "admitted",
                "slot_wait_s", "slot_wait_max_s",
                "run_ahead_chunks", "joined_on_device", "read_wait_s")},
            # the demotion tier's (0 where the engine has none)
            **(tier.counters if tier is not None else TIER_COUNTERS),
            # the page manager's and the stash's own tallies, read and not
            # counted twice (0 where the engine has no such tier)
            **{k: getattr(self.page_mgr, k, 0) for k in (
                "evicted_pages", "demoted_pages", "restored_pages",
                "demote_failed")},
            "demote_last_error": getattr(self.page_mgr, "demote_last_error",
                                         None),
            "stash_spilled_pages": (tier.stash.spilled_pages
                                    if tier is not None else 0),
            # the stash thread's busy seconds: its `stash.put` spans
            "stash_worker_s": (tier.stash.phases.seconds["put"]
                               if tier is not None else 0.0),
        }
        s["stalls"] = list(self._stalls)
        if self.model_cfg.index_topk:
            # decode rows only: a prefill chunk's selection is not counted
            s["sparse"] = dict(
                self._sparse_stats, topk=self.model_cfg.index_topk,
                index_pool_bytes=int(self.cache.idx_pages.nbytes))
        if self.model_cfg.n_experts > 0:
            from ray_tpu.models.moe import gmm_tilings
            # gmm_tilings: which kernel tiles each grouped product shape
            # traced here took (a k tile under k: every visit re-reads)
            s["moe"] = dict(self._moe_stats,
                            recent_decode_syncs=list(self._moe_recent),
                            gmm_tilings=gmm_tilings())
        if (self._stateful or self._windowed) and "moe" in s:
            # pairs that fell on this chip's share of the experts, counted
            # on the device by the programs themselves (read here: a sync)
            held, computed = (int(x) for x in self.cache.held_pairs)
            s["moe"].update(held_pairs=held, computed_rows=computed)
        if self._stateful:
            per_row = lambda xs: sum(int(x.nbytes) // x.shape[0] for x in xs)
            c = self.cache
            s["state"] = dict(
                self.page_mgr.state_stats(), **self._state_stats,
                snapshot_pool_bytes=(per_row(c.snap_state + c.snap_conv)
                                     * self.page_mgr.snapshots),
                slot_state_bytes=(per_row(c.state + c.conv)
                                  * self.config.max_batch_slots))
        if self._windowed:
            c, mgr = self.cache, self.page_mgr
            page_bytes = lambda pool: 2 * int(pool.nbytes) // pool.shape[2]
            full, win = page_bytes(c.k_pages), page_bytes(c.win_k_pages)
            live = mgr.window_stats()
            s["window"] = dict(
                live, **self._window_stats,
                **{k: list(v) for k, v in self._window_recent.items()},
                page_size=self.config.page_size,
                window=self.model_cfg.sliding_window,
                window_pool_bytes=2 * int(c.win_k_pages.nbytes),
                full_pool_bytes=2 * int(c.k_pages.nbytes),
                window_page_bytes=win, full_page_bytes=full,
                live_bytes=(live["window_pages_live"] * win
                            + live["full_pages_live"] * full),
                # what the live rows would hold if every layer kept every key
                live_bytes_one_pool=live["full_pages_live"] * (full + win))
        if self.config.speculate > 0:
            st = dict(self._spec_stats)
            st["accept_rate"] = round(
                st["accepted"] / max(st["drafted"], 1), 4)
            s["speculation"] = st
        if self.page_mgr is not None:
            mgr = self.page_mgr
            s["pages_in_use"] = mgr.pages_in_use
            s["pages_free"] = len(mgr.free_pages)
            s["prefix_cached_pages"] = mgr.cached_pages
            s["prefix_hit_tokens"] = mgr.prefix_hit_tokens
            s["prefix_query_tokens"] = mgr.prefix_query_tokens
            s["prefix_hit_rate"] = round(
                mgr.prefix_hit_tokens / max(mgr.prefix_query_tokens, 1), 4)
        from ray_tpu.util import metrics as _metrics
        s["slo"] = {
            "ttft_s": _metrics.histogram_summary("serve_ttft_s"),
            "tpot_ms": _metrics.histogram_summary("serve_tpot_ms"),
            "batch_occupancy": _metrics.histogram_summary(
                "serve_batch_occupancy"),
            "kv_page_util": _metrics.histogram_summary("serve_kv_page_util"),
            "spill_restore_ms": _metrics.histogram_summary("spill_restore_ms"),
        }
        if self.page_mgr is not None and self.config.prefix_cache:
            mgr = self.page_mgr
            s["radix"] = mgr.node_stats()
            s["radix"]["stash"] = tier.stash.tier_stats()
            s["slo"]["radix"] = {
                "prefix_nodes": mgr.prefix_nodes,
                "prefix_hit_tokens": mgr.prefix_hit_tokens,
                "prefix_evicted_pages": mgr.evicted_pages,
            }
        return s
