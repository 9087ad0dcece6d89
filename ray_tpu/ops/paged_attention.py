"""Paged attention: decode attention over a paged KV cache (pallas/TPU).

Reference contrast: the reference serves LLMs by wrapping vLLM, whose paged
attention is a CUDA kernel walking a per-sequence page table
(vllm PagedAttention; ray serve LLM integration). The TPU-native form:

- KV pages live as one stacked pool `[L, Kh, P, page, D]` in HBM, every
  layer's pages in one array. Whatever reads or writes it addresses it by
  (layer, page): a layer taken out first (`pool[layer]`) is a buffer of its
  own to XLA, 151 MB copied a layer a decode step at Mixtral's sizes, and a
  scatter into it re-tiles all of it (PR 31).
- A block table `[B, max_pages]` maps each sequence's logical pages to pool
  slots; `lengths[B]` counts valid tokens.
- The kernel `paged_decode` takes the block table, the lengths, the layer
  and its WALK as SCALAR-PREFETCH args (pltpu.PrefetchScalarGridSpec). A
  grid step folds a BLOCK of several pages of one row (`pages_per_block`:
  what a VMEM budget holds of the pages' bytes). The pages of a block lie
  anywhere in the pool, so the pool is named as operand once for every page
  of a block, each with an index_map that reads `(layer, table[row, entry])`
  to DMA exactly that page (all kv heads of it) into VMEM while the block
  before is folded: the pallas pipeline does the job of vLLM's manual
  gather, and pages never materialize contiguously.
- The grid is the walk (`_blocks_in_use`): one step for every block a row
  holds keys in, row after row, and its LENGTH is a value of the call, the
  sum of those. A table entry past a row's end gets no step. A grid of
  (row, table entry), a page a step, is 10,240 steps a call at 16 rows of
  640 entries whatever the rows hold, and a page of 64 tokens too small a
  step to hide its own cost; a static grid of (row, block) pays every
  operand's share of a step's cost for the blocks no row uses (v5e, PR 36:
  3.78 and 2.04 ms a call at that shape with rows of 6k-34k keys, against
  1.54).
- Online-softmax accumulation across blocks (same recurrence as
  ops/flash_attention.py) in f32; every kv head folds per block via batched
  dots ([Kh, G, D] x [Kh, block, D]) so the MXU sees one sizable matmul
  instead of Kh tiny ones (a per-head grid ran ~2x slower at decode
  shapes).

Decode is HBM-bandwidth-bound: the win is that only referenced pages move,
so fragmented long-context batches stream at full bandwidth regardless of
slot order. `paged_attention_reference` is the XLA gather equivalent used
for numerics tests and as the CPU fallback.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# VMEM that `paged_decode` gives the pages of a block, K's and V's, each held
# twice (the block being folded and the one in flight): 8 pages a block at
# the 131 KB a page of 8 kv heads x 64 tokens x 128 in bf16. On the v5e
# (PR 36) 16 rows of 6k-34k keys took 2.10 / 1.63 / 1.54 / 1.55 ms a call at
# 2 / 4 / 8 / 16 pages a block (the HBM floor is 1.40), and a row of a few
# pages costs what a whole block's arithmetic costs: 32 rows of one to six
# pages 0.044 / 0.053 / 0.077 / 0.133 ms. Every page of a block is an operand
# of the kernel, K's and V's, so a block has at most `_MAX_PAGES_PER_BLOCK`
# however small a page is.
_KV_BLOCK_BYTES = 4 * 2 ** 20
_MAX_PAGES_PER_BLOCK = 16


def pages_per_block(page_bytes: int, max_pages: int) -> int:
    """Pages of one row that a step of `paged_decode` folds: as many as the
    VMEM budget holds twice for K and twice for V, at least one, at most the
    table's width and `_MAX_PAGES_PER_BLOCK`. `page_bytes`: every kv head's
    share of one page."""
    return max(1, min(max_pages, _MAX_PAGES_PER_BLOCK,
                      _KV_BLOCK_BYTES // (4 * page_bytes)))


def _held(length, room: int):
    """The keys a row is read as holding: a free slot (length 0) reads as
    one, so that every row has a first block and its softmax a finite
    maximum; no more than the table has room for (the reference's mask ends
    there too)."""
    return jnp.clip(length, 1, room)


def _decode_kernel(row_ref, blk_ref, tbl_ref, len_ref, layer_ref, q_ref,
                   *refs, scale, page_size, ppb, window=None):
    """Step s of the grid: fold block blk[s] of row row[s], `ppb` pages, into
    the row's accumulators, for all kv heads at once. The steps are the
    blocks the rows hold keys in, a row's in order (`_blocks_in_use`).

    q_ref, o_ref: [1, Kh, G, D], the row's. refs: the block's `ppb` pages of
    K, then of V, each [Kh, 1, page, D] (the pipeline has copied each into
    its operand: `paged_attention`'s `page_of`), o_ref, and the scratch: the
    running maximum and sum [Kh, G, 128] and the weighted values [Kh, G, D],
    f32.

    A page the row has no key on is whatever its operand held last, a real
    page of the layer; its columns are masked, and 0 x a finite value adds
    nothing.

    With `window` (a sliding layer) the row's walk starts at the block of
    its first visible key, `held - window`, and that block's columns before
    it are masked as the last block's past the row's end are.
    """
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    s_ = pl.program_id(0)
    i = blk_ref[s_]
    block = ppb * page_size
    seq_len = _held(len_ref[row_ref[s_]], tbl_ref.shape[1] * page_size)
    lo = 0 if window is None else jnp.maximum(seq_len - window, 0)

    @pl.when(i == lo // block)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                           # [Kh, G, D]
    k, v = (jnp.concatenate([r[:, 0] for r in pages], axis=1).astype(
        jnp.float32) for pages in (k_refs, v_refs))            # [Kh, block, D]
    s = jax.lax.dot_general(                                   # [Kh, G, block]
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    cols = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    seen = cols < seq_len
    if window is not None:
        seen &= cols >= lo
    s = jnp.where(seen, s, -jnp.inf)
    m_prev, l_prev = m_scr[:, :, :1], l_scr[:, :, :1]
    # every block walked holds a key, so m_new is finite and exp() NaN-free
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
    pv = jax.lax.dot_general(                                  # [Kh, G, D]
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    acc = acc_scr[...] * alpha + pv
    acc_scr[...] = acc
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when((i + 1) * block >= seq_len)
    def _finish():
        o_ref[0] = (acc / l_new).astype(o_ref.dtype)


def _blocks_in_use(lengths, room: int, block: int, n_blocks: int,
                   window: Optional[int] = None):
    """The walk of `paged_decode`: (how many steps [1], the row [S] and the
    block within the row [S] of every step), S = B * n_blocks, the steps
    past the count never run. Row after row, of each the blocks of `block`
    keys it holds keys in (with `window`: visible keys, from the block of
    key `held - window` on). Small arrays and no gather: a row's first step
    is found by comparing and summing."""
    rows = lengths.shape[0]
    held = _held(lengths, room)
    if window is None:
        ends = jnp.cumsum(-(-held // block))                           # [B]
    else:
        low = jnp.maximum(held - window, 0) // block   # a row's first block
        ends = jnp.cumsum(-(-held // block) - low)
    steps = jnp.arange(rows * n_blocks, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(steps[:, None] >= ends[None], axis=1), rows - 1)
    first = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    if window is not None:
        first = first - low
    first = jnp.sum(jnp.where(row[:, None] == jnp.arange(rows)[None],
                              first[None], 0), axis=1)
    return ends[-1:].astype(jnp.int32), row.astype(jnp.int32), steps - first


def paged_attention(
    q: jax.Array,             # [B, H, D] — one decode token per sequence
    k_pages: jax.Array,       # [L, Kh, P, page, D] — the stacked page pool
    v_pages: jax.Array,       # [L, Kh, P, page, D]
    layer,                    # int or int32 scalar — whose pages to read
    block_tables: jax.Array,  # [B, max_pages] int32 — pool slot per page
    lengths: jax.Array,       # [B] int32 — valid tokens per sequence (>= 1)
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Paged decode attention over layer `layer` of the pool; returns
    [B, H, D]. With `window` a row attends to its last `window` tokens, and
    the walk neither copies nor scores a block that lies wholly before them
    (the kernel's name is then `paged_decode_window`).

    The pools go in whole, as the cache holds them: a `pallas_call` operand
    is a buffer of its own, so `k_pages[layer]` handed in would be
    materialised, a layer's whole pool a call. The layer, the block table,
    the lengths and the walk (`_blocks_in_use`) ride as prefetched scalars;
    the grid is as long as the walk, which `lengths` decides: a call's time
    follows the keys the rows hold and not the table's width. A table entry
    past a row's end is never read, so it may hold anything. Sequences
    attend to their first `lengths` tokens.
    """
    b, h, d = q.shape
    _layers, kh, _pool, page_size, _d = k_pages.shape
    g = h // kh
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # the bytes a page takes in VMEM: rows are whole lanes there
    lanes = -(-d // _LANES) * _LANES
    ppb = pages_per_block(kh * page_size * lanes * k_pages.dtype.itemsize,
                          max_pages)
    n_blocks = -(-max_pages // ppb)
    count, row, blk = _blocks_in_use(
        lengths, max_pages * page_size, ppb * page_size, n_blocks,
        **({} if window is None else {"window": window}))

    def row_of(s, row, blk, tbl, lens, lyr):
        return (row[s], 0, 0, 0)

    def page_of(j, s, row, blk, tbl, lens, lyr):
        # operand j holds entry blk * ppb + j of the row's table. Past the
        # row's end it names what it named a step before, the same place of
        # the row's block before: the pipeline copies a block only when its
        # index changes from one step to the next, so such an entry costs no
        # copy. (In a row's first block there is no step before in the row:
        # page 0, the placeholder, one copy for all the short rows in a run.)
        r = row[s]
        last = (_held(lens[r], max_pages * page_size) - 1) // page_size
        entry = blk[s] * ppb + j
        entry = jnp.where(entry <= last, entry, entry - ppb)
        page = jnp.where(entry >= 0, tbl[r, jnp.maximum(entry, 0)], 0)
        return (lyr[0], 0, page, 0, 0)

    rows = pl.BlockSpec((1, kh, g, d), row_of)
    # every kv head's copy of one page of the layer, the layer dimension
    # squeezed; the pool is named once for every page of a block
    pages = [pl.BlockSpec((None, kh, 1, page_size, d),
                          functools.partial(page_of, j)) for j in range(ppb)]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          ppb=ppb,
                          **({} if window is None else {"window": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(count[0],),
            in_specs=[rows] + pages + pages,
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((kh, g, _LANES), jnp.float32),
                            pltpu.VMEM((kh, g, _LANES), jnp.float32),
                            pltpu.VMEM((kh, g, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
        name="paged_decode" if window is None else "paged_decode_window",
    )(row, blk, block_tables, lengths,
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(b, kh, g, d),
      *([k_pages] * ppb), *([v_pages] * ppb))
    return out.reshape(b, h, d)


def _gather_row_pages(pool, layer, block_tables):
    """XLA's gather of every row's pages of `layer`: [B, Kh, mp, page, D].
    The layer rides in the gather. Off the TPU only: on it the compiler
    re-tiles the whole pool for this gather (`row_pages`)."""
    return pool[layer, :, block_tables].swapaxes(1, 2)


def paged_attention_reference(q, k_pages, v_pages, layer, block_tables,
                              lengths, *, scale: Optional[float] = None,
                              window: Optional[int] = None) -> jax.Array:
    """XLA equivalent of `paged_attention`, same arguments (gather pages →
    masked attention): numerics oracle for the kernel and the CPU-backend
    fallback."""
    b, h, d = q.shape
    _layers, kh, _pool, page_size, _d = k_pages.shape
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s_max = block_tables.shape[1] * page_size

    k_seq, v_seq = (_gather_row_pages(pool, layer, block_tables).reshape(
        b, kh, s_max, d) for pool in (k_pages, v_pages))
    qg = q.reshape(b, kh, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_seq.astype(jnp.float32)) * scale
    mask = jnp.arange(s_max)[None, None, None, :] < lengths[:, None, None, None]
    if window is not None:
        mask &= (jnp.arange(s_max)[None, None, None, :]
                 >= lengths[:, None, None, None] - window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v_seq.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV cache: page pool + per-sequence block tables (vLLM's PagedAttention
# memory model, jax-functional — the pool/table are pytree leaves updated
# with pure scatters inside jit; page allocation is host-side bookkeeping,
# serve/radix_cache.py PageManager).
# ---------------------------------------------------------------------------

import flax.struct


class PagedKVCache(flax.struct.PyTreeNode):
    """Per-layer page pools and shared block tables.

    Two layouts, told apart by whether the model has an indexer:

    - dense attention: k_pages/v_pages [L, Kh, P, page, D] (the layout the
      `paged_decode` kernel walks), `idx_pages` None: the pytree and the
      programs of a cache without an indexer are what they were.
    - learned sparse attention: a third pool `idx_pages` (the indexer's one
      key head, Di values a token) and k_pages/v_pages TOKEN-MAJOR,
      [L, P, page, Kh, D]: decode gathers the selected tokens' rows, and a
      gathered unit has to be a whole (Kh, D) tile of the pool or XLA copies
      the layer's pool to re-tile it on every step. For the same reason the
      indexer's pool is [L, P, page / r, r * Di] with r tokens side by side
      in a row of 128 lanes (`index_pack`): at a minor dimension of 64 the
      TPU lays the pool out pages-minor and copies all of it for every
      page gather and scatter (403 MB a layer a prefill chunk, seen in the
      HLO compiled for the v5e). `index_keys` reads pages back as
      [tokens, Di].

    A model with linear-attention layers (`LlamaConfig.full_attn_every`)
    holds two kinds of cache in this one tree. Its pools cover the
    full-attention layers only (L counts those). Each linear layer has, a
    SLOT and not a token, a recurrent state `state[i]` [B, H, dk, dv] in f32
    and the short convolution's last inputs `conv[i]` [B, W - 1, channels],
    carried through prefill chunks and decode steps in place. `snap_state` /
    `snap_conv` ([N, ...] a layer) are a pool of SNAPSHOTS of those, taken
    where a prompt's prefill crosses its last page boundary: a prefix hit on
    such a model is a hit only as far as a snapshot, since the pages' keys
    and values say nothing of what the linear layers had seen
    (`serve/radix_cache.py`). A state is not a page: `pools()` does not list
    these, and what moves pages (demotion, restore, the P/D hand-off) does
    not carry them. `held_pairs` [2] counts, on the device, the (token, expert)
    pairs that fell on this chip's share of the experts: those of real
    tokens, and all that the programs multiplied. All None for every
    other model: their tree and programs are what they were.

    A model with sliding-window layers holds two kinds of PAGE in this one
    tree. `k_pages` / `v_pages` and `block_tables` are the FULL pool: the
    layers that see every key (L counts those), a page for every token of a
    row. `win_k_pages` / `win_v_pages` [Lw, Kh, Pw, page, D] are the WINDOW
    pool of the Lw sliding layers, with a table of its own, `win_tables`
    [B, max_pages], indexed by a position's page as the full table is: a row
    holds window pages only for positions a later query of it can still see,
    an entry before them names whatever page it named last (the kernels
    neither copy nor score a block that lies wholly before the window, and
    mask what lies before it inside the first block), an entry past what the
    row has been given is 0, the placeholder. `window_view()` is the same
    tree with the window pool and table in the full pool's places, so that
    the functions below serve a sliding layer unchanged; `merge_window` puts
    a view's pools back. `pools()` lists the full pool only: a window page is
    not carried by what moves pages, and the engine refuses those paths for
    such a model. All None for every other model.

    block_tables: [B, max_pages]; lengths: [B]. Rows whose slot is free have
    length 0 and table entries 0. `page_axis` is where a pool's page index
    sits: the functions under "Moving whole pages" below are its only
    readers, and whatever moves pages (demotion, restore, the P/D hand-off)
    calls them and so carries every per-page array.
    """
    k_pages: jax.Array
    v_pages: jax.Array
    block_tables: jax.Array
    lengths: jax.Array
    idx_pages: Optional[jax.Array] = None
    state: Optional[tuple] = None
    conv: Optional[tuple] = None
    snap_state: Optional[tuple] = None
    snap_conv: Optional[tuple] = None
    held_pairs: Optional[jax.Array] = None
    win_k_pages: Optional[jax.Array] = None
    win_v_pages: Optional[jax.Array] = None
    win_tables: Optional[jax.Array] = None

    def window_view(self) -> "PagedKVCache":
        """The sliding layers' pool and table where the full layers' are."""
        return self.replace(k_pages=self.win_k_pages, v_pages=self.win_v_pages,
                            block_tables=self.win_tables)

    def merge_window(self, view: "PagedKVCache") -> "PagedKVCache":
        return self.replace(win_k_pages=view.k_pages, win_v_pages=view.v_pages)

    @property
    def page_axis(self) -> int:
        return 2 if self.idx_pages is None else 1

    @property
    def page_size(self):
        return self.k_pages.shape[self.page_axis + 1]

    @property
    def index_dim(self) -> int:
        ps = self.page_size
        return self.idx_pages.shape[-1] * self.idx_pages.shape[-2] // ps

    @property
    def length(self):
        """Alias matching KVCache.length so the decoder's position math is
        cache-type agnostic."""
        return self.lengths

    def pools(self) -> tuple:
        """Every per-page array, in the order hand-offs carry them."""
        kv = (self.k_pages, self.v_pages)
        return kv if self.idx_pages is None else kv + (self.idx_pages,)

    def with_pools(self, pools) -> "PagedKVCache":
        names = ("k_pages", "v_pages", "idx_pages")
        return self.replace(**dict(zip(names, pools)))

    @staticmethod
    def init(n_layers: int, n_kv_heads: int, head_dim: int, num_pages: int,
             page_size: int, batch_slots: int, max_pages_per_seq: int,
             dtype=jnp.bfloat16, index_dim: int = 0,
             linear: Optional[dict] = None,
             window: Optional[dict] = None) -> "PagedKVCache":
        """`n_layers` counts the layers with keys and values (with `window`,
        those that see every key). `linear`: the linear layers' sizes
        (`layers`, `heads`, `key_dim`, `value_dim`, `conv` inputs carried,
        `channels`) and `snapshots`, the pool's size. `window`: the sliding
        layers' pool (`layers`, `num_pages`)."""
        tables = dict(
            block_tables=jnp.zeros((batch_slots, max_pages_per_seq), jnp.int32),
            lengths=jnp.zeros((batch_slots,), jnp.int32))
        if index_dim:
            r = index_pack(page_size, index_dim)
            shape = (n_layers, num_pages, page_size, n_kv_heads, head_dim)
            return PagedKVCache(
                k_pages=jnp.zeros(shape, dtype), v_pages=jnp.zeros(shape, dtype),
                idx_pages=jnp.zeros(
                    (n_layers, num_pages, page_size // r, r * index_dim),
                    dtype), **tables)
        if linear:
            per = lambda n, *shape, dt: tuple(
                jnp.zeros((n,) + shape, dt) for _ in range(linear["layers"]))
            s_shape = (linear["heads"], linear["key_dim"], linear["value_dim"])
            c_shape = (linear["conv"], linear["channels"])
            tables.update(
                state=per(batch_slots, *s_shape, dt=jnp.float32),
                conv=per(batch_slots, *c_shape, dt=dtype),
                snap_state=per(linear["snapshots"], *s_shape, dt=jnp.float32),
                snap_conv=per(linear["snapshots"], *c_shape, dt=dtype),
                held_pairs=jnp.zeros((2,), jnp.int32))
        if window:
            shape = (window["layers"], n_kv_heads, window["num_pages"],
                     page_size, head_dim)
            tables.update(
                win_k_pages=jnp.zeros(shape, dtype),
                win_v_pages=jnp.zeros(shape, dtype),
                win_tables=jnp.zeros((batch_slots, max_pages_per_seq),
                                     jnp.int32),
                held_pairs=jnp.zeros((2,), jnp.int32))
        shape = (n_layers, n_kv_heads, num_pages, page_size, head_dim)
        return PagedKVCache(k_pages=jnp.zeros(shape, dtype),
                            v_pages=jnp.zeros(shape, dtype), **tables)


def copy_slot_state(cache: PagedKVCache, slot, snapshot, save: bool
                    ) -> PagedKVCache:
    """A slot's recurrent state and convolution inputs, every linear layer:
    into snapshot `snapshot` (`save`), or out of it into the slot. Under
    `jit` with the cache donated each is a row read and a row written in
    place. A negative `snapshot` on restore zeroes the slot: a sequence that
    starts from nothing."""
    def move(slots, snaps):
        out_slots, out_snaps = [], []
        for sl, sn in zip(slots, snaps):
            if save:
                row = jax.lax.dynamic_index_in_dim(sl, slot, 0, keepdims=True)
                sn = jax.lax.dynamic_update_slice_in_dim(sn, row, snapshot, 0)
            else:
                row = jax.lax.dynamic_index_in_dim(
                    sn, jnp.maximum(snapshot, 0), 0, keepdims=True)
                row = jnp.where(snapshot < 0, jnp.zeros_like(row), row)
                sl = jax.lax.dynamic_update_slice_in_dim(sl, row, slot, 0)
            out_slots.append(sl)
            out_snaps.append(sn)
        return tuple(out_slots), tuple(out_snaps)

    with jax.named_scope("state_snapshot" if save else "state_restore"):
        state, snap_state = move(cache.state, cache.snap_state)
        conv, snap_conv = move(cache.conv, cache.snap_conv)
    return cache.replace(state=state, conv=conv, snap_state=snap_state,
                         snap_conv=snap_conv)


def index_pack(page_size: int, index_dim: int) -> int:
    """Tokens side by side in one row of the indexer's pool: as many as fill
    128 lanes and divide the page."""
    return math.gcd(page_size, max(1, _LANES // index_dim))


def index_keys(cache: PagedKVCache, layer_idx: int, page_ids) -> jax.Array:
    """The indexer's keys of pages `page_ids` [..., n] as [..., n * page, Di],
    a row a token. The layer index rides in the gather: a slice of the pool
    first would be copied."""
    pages = cache.idx_pages[layer_idx, page_ids]      # [..., n, page/r, r*Di]
    return pages.reshape(page_ids.shape[:-1] + (-1, cache.index_dim))


def write_tokens(cache: PagedKVCache, k_new: jax.Array, v_new: jax.Array,
                 positions: jax.Array) -> PagedKVCache:
    """Scatter new tokens into their pages (jit-safe pure update; the dense
    layout only).

    k_new/v_new: [L, B, T, Kh, D] (T tokens per row this step; T=1 decode,
    T=prompt_len prefill). positions: [B, T] absolute token positions; the
    caller's block table must already map position//page_size for every row.
    Does NOT advance `lengths` — the caller owns admission bookkeeping.
    """
    l, bsz, t, kh, d = k_new.shape
    pos = positions.reshape(-1)                                  # [B*T]
    rows = jnp.repeat(jnp.arange(bsz), t)                        # [B*T]
    page_ids = cache.block_tables[rows, pos // cache.page_size]  # [B*T]
    offs = pos % cache.page_size
    # [L, B, T, Kh, D] → [L, Kh, B*T, D] to line up with pool indexing
    kv = lambda x: x.reshape(l, bsz * t, kh, d).swapaxes(1, 2)
    k_pages = cache.k_pages.at[:, :, page_ids, offs].set(kv(k_new))
    v_pages = cache.v_pages.at[:, :, page_ids, offs].set(kv(v_new))
    return cache.replace(k_pages=k_pages, v_pages=v_pages)


def write_layer_tokens(cache: PagedKVCache, layer_idx: int, k_new: jax.Array,
                       v_new: jax.Array, positions: jax.Array,
                       idx_new: Optional[jax.Array] = None) -> PagedKVCache:
    """Write ONE layer's new tokens into every per-page pool (jit-safe).

    k_new/v_new: [B, T, Kh, D]; idx_new: [B, T, Di], given exactly when the
    cache has an indexer pool; positions: [B, T], a row's T positions
    consecutive (the decoder's: the row's length onward). Layers touch
    disjoint pool slices, so the decoder threads the cache through its
    blocks.

    Every form here writes into the stacked pool in place on the donated
    pool. The token-major layout's scatters write whole (Kh, D) tiles and
    stay in place (PR 28); the dense layout's T > 1 scatter made the v5e's
    compiler re-tile the whole pool and went (PR 31: `_write_chunk_rows`).

    Decode (T == 1) uses per-row dynamic_update_slice, UNROLLED over B: XLA
    aliases it on the donated pool. A fori_loop over the rows compiles
    faster and ran several times slower a step (per-iteration loop overhead
    dominates the tiny writes; a figure from before this installation, not
    re-measured), so the unrolled form stays: its compile cost is one-time
    per (B, shape). Measured in PR 31, `mixtral8x7b-batch` on the v5e (4
    layers, 32 slots, a 604 MB pool, a decode step of 17.0-18.0 ms): the
    256 row writes of a step are `dynamic_update_slice_bf16_4_8_1152_64_128_`,
    0.043 s of 3.45 busy seconds, about 0.25 ms a step.

    The T == 1 path is also the write primitive inside serve/llm's fused
    multi-token decode chunk: the whole PagedKVCache is carried through a
    lax.scan, and because DUS on the carried pool aliases in place, N
    chunked steps cost N per-step writes — no pool copy per scan
    iteration. Keep this path free of ops that break carry aliasing
    (no reshapes of the pool, no scatter).
    """
    bsz, t = k_new.shape[:2]
    ps = cache.page_size
    token_major = cache.idx_pages is not None
    assert (idx_new is not None) == token_major, "indexer key and its pool"
    pools = cache.pools()
    # match the pool's dtype in both branches: scatter casts silently, but
    # dynamic_update_slice requires exact dtype agreement
    news = [n.astype(p.dtype) for n, p in
            zip((k_new, v_new, idx_new)[:len(pools)], pools)]
    if t == 1:
        pools = list(pools)
        for b in range(bsz):  # B is static; one fused program, aliased DUS
            p0 = positions[b, 0]
            page_id = cache.block_tables[b, p0 // ps]
            off = p0 % ps
            for i, new in enumerate(news):
                row = new[b, 0]                      # [Kh, D] or [Di]
                if i == 2:     # r tokens share a row of the indexer's pool
                    di = row.shape[0]
                    r = pools[2].shape[-1] // di
                    start = (layer_idx, page_id, off // r, (off % r) * di)
                    row = row[None, None, None]
                elif token_major:
                    start = (layer_idx, page_id, off, 0, 0)
                    row = row[None, None, None]
                else:
                    start = (layer_idx, 0, page_id, off, 0)
                    row = row[None, :, None, None, :]
                pools[i] = jax.lax.dynamic_update_slice(pools[i], row, start)
        return cache.with_pools(pools)
    if not token_major:
        return cache.with_pools(_write_chunk_rows(
            pools, layer_idx, news, positions[:, 0], cache.block_tables))
    pos = positions.reshape(-1)
    rows = jnp.repeat(jnp.arange(bsz), t)
    page_ids = cache.block_tables[rows, pos // ps]
    offs = pos % ps
    flat = [n.reshape((bsz * t,) + n.shape[2:]) for n in news[:2]]
    return cache.with_pools([
        p.at[layer_idx, page_ids, offs].set(n)
        for p, n in zip(pools[:2], flat)] + [_write_index_rows(
            pools[2], layer_idx, news[2], positions[:, 0],
            cache.block_tables, ps)])


def _write_index_rows(pool, layer_idx: int, new, first_pos, tables, ps: int):
    """The indexer's keys of a prefill chunk ([B, T, Di]) into its pool
    [L, P, page / r, r * Di] at positions first_pos[b] .. first_pos[b] + T - 1
    of row b, page by page as `_write_chunk_rows` writes the dense layout: a
    page is read by (layer, page), seen as [page, Di], the chunk's rows laid
    over it and written back, in place on the donated pool; a slot that holds
    no token of the chunk, or lies past the table's end, keeps its old row.

    A token's Di values are half a row of the pool, and the scatter this
    replaces (a window of Di lanes at (layer, page, row, first lane) a token)
    the v5e's compiler ran as a loop of one `dynamic-update-slice` a token:
    2048 of them a chunk of 512 x 4 layers, 9.6 ms of its 46 (PR 44, traced
    on the chip); page by page it is 9 writes a layer."""
    bsz, t, di = new.shape
    rows, lanes = pool.shape[-2:]
    mp = tables.shape[1]
    n_pages = (t + 2 * ps - 2) // ps
    padded = jnp.pad(new, ((0, 0), (ps, n_pages * ps - t), (0, 0)))
    slot = jnp.arange(ps)
    for b in range(bsz):  # B and the page count are static: one program
        first, off0 = first_pos[b] // ps, first_pos[b] % ps
        for j in range(n_pages):
            token = j * ps - off0 + slot         # the chunk's token a slot
            keep = (token >= 0) & (token < t) & (first + j < mp)
            start = (layer_idx, tables[b, jnp.minimum(first + j, mp - 1)],
                     0, 0)
            fresh = jax.lax.dynamic_slice_in_dim(
                padded[b], j * ps - off0 + ps, ps, axis=0)        # [page, Di]
            old = jax.lax.dynamic_slice(pool, start, (1, 1, rows, lanes))
            page = jnp.where(keep[:, None], fresh, old.reshape(ps, di))
            pool = jax.lax.dynamic_update_slice(
                pool, page.reshape(1, 1, rows, lanes), start)
    return pool


def _write_chunk_rows(pools, layer_idx: int, news, first_pos, tables) -> list:
    """The dense layout's prefill write: rows `news` ([B, T, Kh, D] a pool)
    at positions first_pos[b] .. first_pos[b] + T - 1 of row b, page by page.

    T consecutive tokens touch at most ceil((T + page - 1) / page) pages, the
    first and the last in part, so each page is read out of the stacked pool
    with the layer and the page in `start`, the chunk's rows are laid over
    it, and it is written back to the same `start`: a dynamic_slice and a
    dynamic_update_slice of one page, which XLA does in place on the donated
    pool as it does decode's single rows. Where a page's slot holds no token
    of the chunk (before the first position, past the last, or past the
    table's end) the old row goes back.

    What decided the form (PR 31, HLO compiled for the v5e at Mixtral's
    sizes): the scatter this replaces (`.at[layer, :, pages, offs].set`) had
    the compiler re-tile the whole pool into the scatter's layout
    (`{4,1,3,2,0}`) and back, four copies of all layers' pool a chunk
    (`copy_bf16_4_8_1152_64_128_`, 1.47 ms each, 0.15-0.21 s of a 3.7 s
    trace); this form compiles to 72 in-place `dynamic-update-slice` fusions
    a 512-token chunk (9 pages x K, V x 4 layers) and no other instruction
    the size of a pool, and on the chip no pool-sized copy is left in the
    trace. A prefill chunk of the 16-layer Mistral-7B replica (a 2.4 GB
    pool) went from 46.1 to 14.5 ms of device time (`step.prefill_chunk_ms`,
    one traced run a side).
    """
    bsz, t = news[0].shape[:2]
    kh, _, ps, d = pools[0].shape[1:]
    mp = tables.shape[1]
    n_pages = (t + 2 * ps - 2) // ps
    # [B, T, Kh, D] -> [B, Kh, page + n_pages * page, D]: every page's window
    # of the chunk is one dynamic_slice along the padded token dimension
    padded = [jnp.pad(n.swapaxes(1, 2),
                      ((0, 0), (0, 0), (ps, n_pages * ps - t), (0, 0)))
              for n in news]
    slot = jnp.arange(ps)
    pools = list(pools)
    for b in range(bsz):  # B and the page count are static: one program
        first, off0 = first_pos[b] // ps, first_pos[b] % ps
        for j in range(n_pages):
            token = j * ps - off0 + slot         # the chunk's token a slot
            keep = (token >= 0) & (token < t) & (first + j < mp)
            page_id = tables[b, jnp.minimum(first + j, mp - 1)]
            start = (layer_idx, 0, page_id, 0, 0)
            for i, rows in enumerate(padded):
                new = jax.lax.dynamic_slice_in_dim(
                    rows[b], j * ps - off0 + ps, ps, axis=1)   # [Kh, page, D]
                old = jax.lax.dynamic_slice(pools[i], start,
                                            (1, kh, 1, ps, d))
                page = jnp.where(keep[:, None], new[None, :, None], old)
                pools[i] = jax.lax.dynamic_update_slice(pools[i], page, start)
    return pools


# ---------------------------------------------------------------------------
# Moving whole pages. The only functions that index a pool by its page axis:
# whatever takes pages out of the pools or puts them in (demotion, restore,
# the P/D hand-off, a prefill continuation's row gather) calls these, so a
# cache kind with another set of per-page arrays changes this file and the
# model's block, and nothing that carries pages.
# ---------------------------------------------------------------------------

def page_layout(cache: PagedKVCache) -> list:
    """What one page of `cache` consists of: for each per-page pool, in the
    order of `pools()`, its block's shape (the pool's without the page
    dimension) and type, and `axis`, where an array of n pages in the pool's
    own form holds them. Plain values: a shipment's header carries the list
    (the receiving cache's must equal it) and a stash handle records it."""
    axis = cache.page_axis
    return [{"shape": [int(d) for i, d in enumerate(p.shape) if i != axis],
             "axis": axis, "dtype": str(p.dtype)} for p in cache.pools()]


def gather_pages(cache: PagedKVCache, idx, page_major: bool = True) -> tuple:
    """Pages `idx` [n] of every per-page pool as buffers of their own:
    page-major ([n, *block shape]), so that each page is contiguous on the
    host, or in the pool's own form with the n pages where the pool has its
    pages ([L, Kh, n, ps, D] in the dense layout: a shipment's bytes)."""
    axis = cache.page_axis
    if page_major and axis == 1:
        # [L, P, ...] pools: the layer rides in the gather and the pages
        # come out first. A take along axis 1 and a moveaxis copied both
        # 3.2 GB pools a group of 8 pages (20 ms, HLO and trace on the v5e,
        # PR 28)
        return tuple(
            pool[jnp.arange(pool.shape[0])[None, :], idx[:, None]]
            for pool in cache.pools())
    taken = (jnp.take(pool, idx, axis=axis, mode="clip")
             for pool in cache.pools())
    return tuple(jnp.moveaxis(t, axis, 0) if page_major else t for t in taken)


def scatter_pages(cache: PagedKVCache, idx, blocks,
                  page_major: bool = True) -> PagedKVCache:
    """Pages `idx` [n] of every pool <- `blocks`, one array a pool in the
    form `gather_pages` gives for the same `page_major`. Under `jit` with the
    cache donated XLA writes the pools in place; an eager call copies every
    whole pool."""
    pools = cache.pools()
    if len(blocks) != len(pools):
        raise ValueError(f"{len(blocks)} arrays a page were handed over, "
                         f"this cache holds {len(pools)}")
    axis = cache.page_axis
    if page_major and axis == 1:
        # as in gather_pages: the layer rides in the scatter. The moveaxis
        # form copies a whole pool on the v5e (3.2 GB of temporaries in the
        # program compiled for it at the Keye cell's sizes, PR 30)
        return cache.with_pools([
            pool.at[jnp.arange(pool.shape[0])[None, :, None],
                    idx[:, None, None],
                    jnp.arange(pool.shape[2])[None, None, :]].set(block)
            for pool, block in zip(pools, blocks)])
    at = (slice(None),) * axis + (idx,)
    return cache.with_pools([
        pool.at[at].set(jnp.moveaxis(block, 0, axis) if page_major else block)
        for pool, block in zip(pools, blocks)])


def _copy_pages_kernel(tbl_ref, layer_ref, k_ref, v_ref, ko_ref, vo_ref):
    ko_ref[...] = k_ref[...]
    vo_ref[...] = v_ref[...]


def row_pages(cache: PagedKVCache, layer_idx: int,
              interpret: Optional[bool] = None, first=None,
              n_pages: Optional[int] = None):
    """Layer `layer_idx`'s keys and values of every row's pages, contiguous
    by position and HEAD-MAJOR as the pool lies ([B, Kh, mp, page, D] each,
    what `flash_continuation` takes): token s of a row's (mp, page) is
    absolute position s, and the padded table's placeholder pages sit past
    every valid position.

    On the TPU the kernel `paged_row_pages` copies the pages out: grid
    (B, mp), the table and the layer prefetched, the page's block chosen by
    (layer, table[b, p]). A `pallas_call` holds its operand to the layout
    the pool lies in. In XLA's own hands the pool did not stay there: with
    the layer taken out first it copied the layer's pool, and for a gather
    or per-page dynamic_slices of the stacked pool, however spelt, the
    v5e's compiler re-tiled ALL of it pages-major to suit the attention
    that consumes the rows, and back for the result (four copies of all
    layers' pool a continuation chunk: PR 31, HLO compiled for the v5e).
    `interpret` None: the kernel on the TPU, XLA's gather elsewhere.

    `first` [B] and `n_pages` (a sliding layer's continuation): the kernel
    (`paged_row_pages_window`) copies only the `n_pages` pages from table
    entry `first` on, to the same places of the result; what the result holds
    elsewhere is not written and must not be read. XLA's gather takes every
    entry as ever.
    """
    tb = cache.block_tables            # [B, mp]
    b, mp = tb.shape
    kh, _, ps, d = cache.k_pages.shape[1:]
    if interpret is None and jax.default_backend() != "tpu":
        k, v = (_gather_row_pages(pool, layer_idx, tb)
                for pool in (cache.k_pages, cache.v_pages))
    elif first is not None:
        at = lambda b_, p_, first: jnp.minimum(first[b_] + p_, mp - 1)
        page = pl.BlockSpec(
            (None, kh, 1, ps, d),
            lambda b_, p_, tbl, lyr, first: (
                lyr[0], 0, tbl[b_, at(b_, p_, first)], 0, 0))
        out = pl.BlockSpec(
            (None, kh, 1, ps, d),
            lambda b_, p_, tbl, lyr, first: (b_, 0, at(b_, p_, first), 0, 0))
        shape = jax.ShapeDtypeStruct((b, kh, mp, ps, d), cache.k_pages.dtype)
        k, v = pl.pallas_call(
            lambda tbl, lyr, first, *refs: _copy_pages_kernel(tbl, lyr, *refs),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b, min(n_pages, mp)),
                in_specs=[page, page], out_specs=[out, out]),
            out_shape=[shape, shape],
            interpret=bool(interpret),
            name="paged_row_pages_window",
        )(tb, jnp.asarray(layer_idx, jnp.int32).reshape(1),
          first.astype(jnp.int32), cache.k_pages, cache.v_pages)
    else:
        page = pl.BlockSpec(
            (None, kh, 1, ps, d),
            lambda b_, p_, tbl, lyr: (lyr[0], 0, tbl[b_, p_], 0, 0))
        out = pl.BlockSpec((None, kh, 1, ps, d),
                           lambda b_, p_, tbl, lyr: (b_, 0, p_, 0, 0))
        shape = jax.ShapeDtypeStruct((b, kh, mp, ps, d), cache.k_pages.dtype)
        k, v = pl.pallas_call(
            _copy_pages_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b, mp),
                in_specs=[page, page], out_specs=[out, out]),
            out_shape=[shape, shape],
            interpret=bool(interpret),
            name="paged_row_pages",
        )(tb, jnp.asarray(layer_idx, jnp.int32).reshape(1),
          cache.k_pages, cache.v_pages)
    return k, v


def row_keys_values(cache: PagedKVCache, layer_idx: int,
                    interpret: Optional[bool] = None):
    """`row_pages` in the dense layout ([B, mp * page, Kh, D] each), for the
    XLA forms of attention."""
    k, v = row_pages(cache, layer_idx, interpret)
    b, kh, mp, ps, d = k.shape
    to_rows = lambda x: x.reshape(b, kh, mp * ps, d).swapaxes(1, 2)
    return to_rows(k), to_rows(v)


# ---------------------------------------------------------------------------
# Learned sparse attention over the paged cache (a lightning indexer's top-k
# selection, DeepSeek-V3.2-Exp style): the indexer scores every cached key of
# a row, the `topk` best are selected (all of them while the row holds `topk`
# or fewer), and attention runs over the selected keys only. XLA on the
# token-major layout, but for the decode step's scores, which a kernel
# computes from the indexer's pool where it lies (`sparse_decode_scores`);
# one selection a query token, shared by every head.
# ---------------------------------------------------------------------------

_NEG = -1e30       # masked attention logit: finite, so no row turns to NaN


def index_scores(qi, wi, ki):
    """I[.., t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) in f32.
    qi [.., T, J, Di], wi [.., T, J], ki [.., S, Di] -> [.., T, S]."""
    s = jnp.einsum("...tjd,...sd->...tjs", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...tjs,...tj->...ts", jax.nn.relu(s),
                      wi.astype(jnp.float32))


# Bits a pass of `kth_largest` settles. A pass compares every key with the
# 2^bits - 1 candidates of its digit and reads the keys once: at 4 bits the
# compares bound it (2.08 ms a layer for [512, 29696] on the v5e, PR 44), at
# 2 bits the 16 reads do.
_SELECT_BITS = 3
_NEG_INF_KEY = 0x007FFFFF     # -inf in `kth_largest`'s order of the floats


def kth_largest(scores, k: int, bits: int = _SELECT_BITS):
    """The k-th largest of each row of `scores` [T, S] (f32, -inf allowed),
    exactly, as a radix select over the floats' bits, `bits` at a pass from
    the top: each pass is one read of the scores that counts, for the
    2^bits - 1 candidate prefixes, how many keys reach it. Returns (keys
    [T, S], kth [T, 1]) as uint32 whose order is the floats'. `lax.top_k` of a
    [512, 30720] block is a full sort on the TPU: 14 ms of a prefill chunk's
    layer against 2 ms this way (v5e, PR 28); decode, which wants the
    positions too, gets them from the value (`top_k_places`)."""
    as_int = jax.lax.bitcast_convert_type(scores, jnp.int32)
    as_int = jnp.where(as_int < 0, as_int ^ 0x7FFFFFFF, as_int)
    keys = jax.lax.bitcast_convert_type(as_int, jnp.uint32) ^ jnp.uint32(1 << 31)
    kth = jnp.zeros((scores.shape[0], 1), jnp.uint32)
    for top in range(32, 0, -bits):
        shift = max(top - bits, 0)
        digits = jnp.arange(1, 1 << (top - shift), dtype=jnp.uint32)
        cands = kth | (digits[None, :] << shift)                  # [T, 2^b-1]
        reach = (keys[:, :, None] >= cands[:, None, :]).sum(1)
        best = (reach >= k).sum(-1, keepdims=True).astype(jnp.uint32)
        kth = kth | (best << shift)    # reach falls as the candidate grows
    return keys, kth


# Pages of one row that a step of `sparse_decode_scores` copies and scores, and
# how many of them one stretch of straight code starts (a block whole, then
# what is left of a row's last block in eights and ones). A page of the
# indexer's pool is 8 KB at Keye's sizes (64 tokens x 64 values in bf16): a
# block is small in VMEM whatever these are, and what a call costs is the
# START of a copy a page, not its bytes. On the v5e (PR 48), 24 rows of
# 16k-28k keys, 7,700-7,900 pages a call whose bytes' time is 0.08 ms: 0.40
# with a loop of one start a turn at 16 pages a block, 0.26 with a block's
# starts in straight code, 0.22 / 0.19 at 32 / 64 pages a block; 0.16 of the
# 0.22 with nothing scored, 0.06 with nothing copied: 20 ns a page, what
# XLA's own gather of the pages took too, and the two add. Starts laid
# between the pieces of a block's product gained nothing.
_INDEX_PAGES_PER_BLOCK = 32
_INDEX_COPY_STRETCHES = (8, 1)


def index_block_keys(page_size: int, max_pages: int) -> int:
    """Keys a step of `sparse_decode_scores` scores: a row's walk covers its
    length rounded up to this."""
    return min(_INDEX_PAGES_PER_BLOCK, max_pages) * page_size


def _scores_kernel(tbl_ref, pages_ref, slot_ref, layer_ref, q_ref, w_ref,
                   pool_ref, o_ref, buf, sem, *, ppb, stretches, heads):
    """Row b of the grid: the indexer's scores of every key the row holds,
    block after block of `ppb` pages, a step only for a block that holds
    keys of the row (a free slot runs none and reads nothing).

    pages_ref [B]: the pages each row holds keys on; slot_ref [B]: the half
    of `buf` a row's first block lies in (the rows' blocks alternate between
    the halves right through the call). q_ref [1, r * J, r * Di]: the row's
    J query heads laid out block-diagonally, once for each of the r tokens
    that share a row of the pool, so that a packed row is scored as it lies:
    [r * J, r * Di] x [n, r * Di]^T is [r * J, n], token s of pool row i
    under rows s * J .. s * J + J - 1. w_ref [1, r * J, 1] f32: the heads'
    weights, r times. pool_ref: the indexer's pool whole, [L, P, page / r,
    r * Di], left in HBM: the kernel copies the pages a block names in the
    table itself (buf [2, ppb, page / r, r * Di], one DMA semaphore a half),
    the next block's while this one is scored, and while a row's last block
    is scored the first of the row after it. o_ref [1, n_blocks, r, n],
    n = ppb x page / r: o[0, blk, s, i] is the score of position
    (blk * n + i) * r + s. A block the row has no key in is not written, and
    a page past the row's last is not copied: what the result holds there is
    left over, for the caller to mask."""
    b = pl.program_id(0)
    _, _, rows, lanes = buf.shape
    n = ppb * rows
    r = q_ref.shape[1] // heads
    blocks_of = lambda row: pl.cdiv(pages_ref[row], ppb)
    n_blocks = blocks_of(b)
    layer = layer_ref[0]

    def each_page(row, blk, slot, start: bool, stretches=stretches):
        """Start, or wait for, the copy of every page of block `blk` of
        `row` that the row holds keys on, in stretches of straight code:
        as many of `stretches[0]` pages as fit, then of the next size, down
        to 1. A wait reads its size alone, so one wait covers a stretch."""
        def stretch(first, size):
            if not start:
                at = buf.at[slot, pl.ds(0, size)]
                pltpu.make_async_copy(at, at, sem.at[slot]).wait()
                return
            for j in range(size):
                pltpu.make_async_copy(
                    pool_ref.at[layer, pl.ds(
                        tbl_ref[row, blk * ppb + first + j], 1)],
                    buf.at[slot, pl.ds(first + j, 1)], sem.at[slot]).start()

        here = jnp.minimum(pages_ref[row] - blk * ppb, ppb)
        done = 0
        for size in stretches:
            count = (here - done) // size
            jax.lax.fori_loop(
                0, count, lambda g, carry, size=size, done=done:
                (stretch(done + g * size, size), carry)[1], 0)
            done = done + count * size

    @pl.when((n_blocks > 0) & ((b == 0) | (blocks_of(jnp.maximum(b - 1, 0))
                                           == 0)))
    def _first():
        # no row before this one to have started its copies: the first row
        # and one after a free slot (the short stretches: the kernel's
        # straight code is what a program's lowering pays for, a layer)
        each_page(b, 0, slot_ref[b], True, stretches[1:] or stretches)

    after = jnp.minimum(b + 1, pl.num_programs(0) - 1)

    def block(blk, carry):
        slot = (slot_ref[b] + blk) % 2
        # copied while this block is scored: the row's next block or, behind
        # its last, the first block of the row after it
        last = blk + 1 == n_blocks

        @pl.when(jnp.logical_not(last) | ((after > b) & (blocks_of(after) > 0)))
        def _ahead():
            each_page(jnp.where(last, after, b), jnp.where(last, 0, blk + 1),
                      1 - slot, True)

        each_page(b, blk, slot, False)
        keys = buf[slot].reshape(n, lanes)
        s = jax.lax.dot_general(q_ref[0], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0]                        # [r * J, n]
        o_ref[0, blk] = s.reshape(r, heads, n).sum(axis=1)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)


def sparse_decode_scores(qi, wi, cache: PagedKVCache, layer_idx, lengths, *,
                         interpret: Optional[bool] = None):
    """The indexer's score of every cached key of each decode row, [B, S] in
    f32 by position (S the table's width in tokens), -inf at and past a
    row's length: `index_scores` of the row's query over `index_keys` of its
    table, without the keys' copy. qi [B, J, Di], wi [B, J], lengths [B].

    The kernel `sparse_decode_scores` takes the indexer's pool whole, as the
    cache holds it, and the table, the rows' pages and the layer as
    prefetched scalars; a grid step is a row, which walks its own pages in
    blocks (`_scores_kernel`): what a call reads and multiplies follows the
    keys the rows hold, not the table's width. bf16 operands, f32
    accumulation, relu, the heads' weights and their sum in f32. `interpret`
    None: compiled on the TPU, interpreted elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mp = cache.block_tables.shape[1]
    ppb = min(_INDEX_PAGES_PER_BLOCK, mp)
    stretches = (ppb,) + tuple(n for n in _INDEX_COPY_STRETCHES if n < ppb)
    return _decode_scores(
        qi, wi, cache.idx_pages, cache.block_tables, lengths,
        jnp.asarray(layer_idx, jnp.int32), page_size=cache.page_size, ppb=ppb,
        stretches=stretches, interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("page_size", "ppb", "stretches",
                                             "interpret"))
def _decode_scores(qi, wi, pool, tables, lengths, layer, *, page_size, ppb,
                   stretches, interpret):
    """`sparse_decode_scores` with the layer a value: a program of its own
    under `jit`, so that the layers of a model trace and lower ONE kernel
    between them (its straight code is long: 0.2 s a lowering, and a cell's
    set-up lowers the decode chunk some ten times)."""
    b, heads, di = qi.shape
    _layers, _pool, rows, lanes = pool.shape
    r = lanes // di
    ps, mp = page_size, tables.shape[1]
    n_blocks, n = -(-mp // ppb), ppb * rows
    pages = jnp.clip(-(-lengths.astype(jnp.int32) // ps), 0, mp)
    blocks = -(-pages // ppb)
    first_slot = (jnp.cumsum(blocks) - blocks) % 2
    # [B, r * J, r * Di]: head j of token s reads the s-th Di lanes of a row
    q = jnp.einsum("st,bjd->bsjtd", jnp.eye(r, dtype=qi.dtype), qi).reshape(
        b, r * heads, lanes).astype(pool.dtype)
    w = jnp.tile(wi.astype(jnp.float32), (1, r))[:, :, None]
    of_row = lambda i, *scalars: (i, 0, 0)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, ppb=ppb, heads=heads,
                          stretches=stretches),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, r * heads, lanes), of_row),
                      pl.BlockSpec((1, r * heads, 1), of_row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_blocks, r, n),
                                   lambda i, *scalars: (i, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, ppb, rows, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_blocks, r, n), jnp.float32),
        interpret=interpret,
        name="sparse_decode_scores",
    )(tables, pages, first_slot.astype(jnp.int32), layer.reshape(1), q, w,
      pool)
    scores = out.swapaxes(2, 3).reshape(b, n_blocks * n * r)[:, :mp * ps]
    return jnp.where(jnp.arange(mp * ps)[None] < lengths[:, None], scores,
                     -jnp.inf)


def _kept(scores, k: int):
    """`lax.top_k`'s own set of each row of `scores` [T, S] as a mask: all
    above the k-th value (`kth_largest`: no sort), and of those equal to it
    the first by position (scores tie at 0, where every head's relu is
    shut); a -inf is no key and is never kept, so a row of fewer than k keys
    keeps them all. The count along a row that ranks the tied is the dear
    part (1.7 ms a layer for [512, 29696] on the v5e, PR 44), and only a row
    with more keys tied at its k-th value than it has room for needs it; one
    whose k-th value is -inf does not: what is tied there is no key at all."""
    keys, kth = kth_largest(scores, k)
    above, tied = keys > kth, keys == kth
    room = k - above.sum(-1, keepdims=True)
    surplus = (tied.sum(-1, keepdims=True) > room) & (kth > _NEG_INF_KEY)
    selected = jax.lax.cond(
        surplus.any(),
        lambda: above | (tied & (jnp.cumsum(tied, -1) <= room)),
        lambda: above | tied)
    return selected & (keys > _NEG_INF_KEY)


@functools.partial(jax.jit, static_argnames=("k", "page_size"))
def top_k_places(scores, tables, k: int, page_size: int):
    """Where the k best keys of each row lie in the pool, found by value,
    without a sort, a scatter or a scalar gather: (page_ids [B, K], offsets
    [B, K], chosen [B, K]) for scores [B, S] in f32 (-inf: no key) and
    tables [B, S / page_size]. The set is `lax.top_k`'s own (`_kept`: ties go
    to the earlier position); slot j of a row is its j-th kept key by
    position, and a row of fewer than k keys leaves the rest of its slots
    not `chosen` (page 0, offset 0).

    A count a page and a running sum over the pages give each page the range
    of slots it fills; a slot's page is the one whose range holds it, found
    by comparison, as a one-hot [B, K, mp]; one contraction with it on the
    MXU yields the page's id and the range's start (in base-128 digits) and
    the page's kept keys as a bit a token, eight to a byte. The slot's
    offset is the place of the bit whose rank among the page's set bits is
    the slot's rank inside the page: the byte by the bytes' counts, the bit
    by the counts of the byte's low bits. Exact: every sum has one term, and
    a term is an integer below 256, which bf16 holds. (XLA's gather of
    49,152 scalars, a page id a slot, took 0.50 ms a layer a decode step on
    the v5e, PR 28, and `lax.top_k` of [24, 29696] is a full sort, 0.58 ms;
    this is under 0.2, PR 48.) Under `jit`, as `_decode_scores` is: a
    model's layers lower it once between them."""
    b, s_max = scores.shape
    mp = tables.shape[1]
    n_bytes = page_size // 8
    assert s_max == mp * page_size and page_size % 8 == 0 and k < 1 << 14
    bits = _kept(scores, k).reshape(b, mp, n_bytes, 8).astype(jnp.int32)
    kept_bytes = (bits << jnp.arange(8)).sum(-1)                # [B, mp, page/8]
    count = bits.sum((2, 3))                                       # [B, mp]
    ends = jnp.cumsum(count, axis=1)
    starts = ends - count
    slots = jnp.arange(k)[None, :, None]
    one_hot = ((starts[:, None] <= slots) & (slots < ends[:, None])
               ).astype(jnp.bfloat16)                              # [B, K, mp]
    digits = [(x >> shift) & 127 for x, shifts in
              ((tables, (0, 7, 14, 21)), (starts, (0, 7)))
              for shift in shifts]
    of_page = jnp.concatenate([jnp.stack(digits, -1), kept_bytes], -1)
    got = jnp.einsum("bkp,bpc->bkc", one_hot, of_page.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    page_ids = (got[..., 0] | (got[..., 1] << 7) | (got[..., 2] << 14)
                | (got[..., 3] << 21))
    rank = jnp.arange(k)[None] - (got[..., 4] | (got[..., 5] << 7))
    kept_bytes = got[..., 6:]                                  # [B, K, page/8]
    held = jax.lax.population_count(kept_bytes)
    byte_at = (jnp.cumsum(held, -1) <= rank[..., None]).sum(-1)    # [B, K]
    this, earlier = (jnp.arange(n_bytes) == byte_at[..., None],
                     jnp.arange(n_bytes) < byte_at[..., None])
    rank = rank - jnp.where(earlier, held, 0).sum(-1)
    low_bits = (jnp.where(this, kept_bytes, 0).sum(-1)[..., None]
                & ((2 << jnp.arange(8)) - 1))                      # [B, K, 8]
    bit_at = (jax.lax.population_count(low_bits) <= rank[..., None]).sum(-1)
    chosen = jnp.arange(k)[None] < ends[:, -1:]
    return page_ids, jnp.where(chosen, byte_at * 8 + bit_at, 0), chosen


def sparse_paged_decode(q, qi, wi, cache: PagedKVCache, layer_idx: int,
                        lengths, topk: int, *, scale=None,
                        interpret: Optional[bool] = None):
    """One decode token a row over its `topk` selected cached keys.

    q [B, H, D]; qi [B, J, Di], wi [B, J]: the indexer's query heads and
    their weights; lengths [B]: valid tokens, this step's included (its
    keys are already written). Returns [B, H, D]. Shape-stable and free of
    host callbacks: it is the body of the fused decode scan. A row of `topk`
    tokens or fewer selects every valid key (the rest of the static top-k's
    slots are not `chosen` and get no weight), so it attends to everything.

    The selection is computed from the pool where it lies, once
    (`sparse_decode_scores`), and its k best are found by value
    (`top_k_places`): the set is `lax.top_k`'s, ties to the earlier
    position, in the order of the positions.
    """
    b, h, d = q.shape
    kh = cache.k_pages.shape[-2]
    ps, tb = cache.page_size, cache.block_tables
    k = min(topk, tb.shape[1] * ps)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    with jax.named_scope("sparse_index"):
        scores = sparse_decode_scores(qi, wi, cache, layer_idx, lengths,
                                      interpret=interpret)         # [B, S]
    with jax.named_scope("sparse_select"):
        page_ids, offsets, chosen = top_k_places(scores, tb, k, ps)  # [B, K]
        k_sel = cache.k_pages[layer_idx, page_ids, offsets]       # [B,K,Kh,D]
        v_sel = cache.v_pages[layer_idx, page_ids, offsets]
    with jax.named_scope("sparse_attend"):
        qg = q.reshape(b, kh, h // kh, d)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_sel,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen[:, None, None, :], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_sel.dtype), v_sel)
    return out.reshape(b, h, d).astype(q.dtype)


def sparse_paged_prefill(q, qi, wi, cache: PagedKVCache, layer_idx: int,
                         positions, topk: int, *, key_block: int = 1024,
                         scale=None):
    """A chunk of queries over each row's cached prefix and the chunk itself
    (already written), by key blocks: nothing of size [T, heads, context]
    is held. Two passes over the row's pages, each as far as the last query
    reaches: the indexer's scores [T, S] (f32, no head axis), from which each
    query's `topk` best are marked; then attention with an online softmax in
    which a key counts iff it is marked: the selection as a mask, the same
    mathematics as gathering.

    q [B, T, H, D]; qi [B, T, J, Di]; wi [B, T, J]; positions [B, T]
    absolute. Returns [B, T, H, D].
    """
    bsz, t, h, d = q.shape
    kh = cache.k_pages.shape[-2]
    g = h // kh
    ps = cache.page_size
    ppb = max(1, key_block // ps)              # pages a key block
    kb = ppb * ps
    mp = cache.block_tables.shape[1]
    n_static = -(-mp // ppb)
    s_pad = n_static * kb
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k_pool, v_pool, _ = cache.pools()

    tables = jnp.pad(cache.block_tables, ((0, 0), (0, n_static * ppb - mp)))
    n_blocks = (jnp.max(positions) + kb) // kb   # blocks the last query reaches

    def index_block(i, scores):
        pages = jax.lax.dynamic_slice_in_dim(tables, i * ppb, ppb, 1)
        ki = index_keys(cache, layer_idx, pages)                  # [B, kb, Di]
        s = index_scores(qi, wi, ki)                              # [B, T, kb]
        s = jnp.where((i * kb + jnp.arange(kb))[None, None]
                      <= positions[:, :, None], s, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(scores, s, i * kb, 2)

    with jax.named_scope("sparse_index"):
        scores = jax.lax.fori_loop(
            0, n_blocks, index_block,
            jnp.full((bsz, t, s_pad), -jnp.inf, jnp.float32))
    with jax.named_scope("sparse_select"):
        selected = _kept(scores.reshape(bsz * t, s_pad),
                         min(topk, s_pad)).reshape(bsz, t, s_pad)

    def attend_row(q, selected, table):
        qg = q.reshape(t, kh, g, d)

        def attend_block(i, carry):
            m, l, acc = carry
            pages = jax.lax.dynamic_slice_in_dim(table, i * ppb, ppb)
            k = k_pool[layer_idx, pages].reshape(kb, kh, d)
            v = v_pool[layer_idx, pages].reshape(kb, kh, d)
            keep = jax.lax.dynamic_slice_in_dim(selected, i * kb, kb, 1)
            s = jnp.einsum("tkgd,skd->kgts", qg, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep[None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(keep[None, None], jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "kgts,skd->kgtd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((kh, g, t), _NEG, jnp.float32),
                jnp.zeros((kh, g, t), jnp.float32),
                jnp.zeros((kh, g, t, d), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n_blocks, attend_block, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None]              # [Kh,G,T,D]
        return out.transpose(2, 0, 1, 3).reshape(t, h, d).astype(q.dtype)

    with jax.named_scope("sparse_attend"):
        return jax.vmap(attend_row)(q, selected, tables)


def sparse_attention_reference(q, k, v, qi, ki, wi, topk: int, *, scale=None):
    """The same selection without a cache, all at once (the CPU oracle and
    the model's uncached forward; holds [B, T, T] scores, so short
    sequences only). q [B, T, H, D]; k, v [B, T, Kh, D]; qi [B, T, J, Di];
    ki [B, T, Di]; wi [B, T, J]."""
    from ray_tpu.ops.attention import mha_reference
    t = q.shape[1]
    if t <= topk:
        return mha_reference(q, k, v, causal=True, scale=scale)
    scores = index_scores(qi, wi, ki)                             # [B, T, T]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    sel = jax.lax.top_k(scores, topk)[1]                          # [B, T, K]
    keep = jnp.zeros(scores.shape, bool).at[
        jnp.arange(q.shape[0])[:, None, None], jnp.arange(t)[None, :, None],
        sel].set(True) & causal
    return mha_reference(q, k, v, causal=False, mask=keep, scale=scale)
