"""On the chip, at the Keye cell's shape: what finds a decode step's selected
keys, part by part, alone.

    python3 tools/sparse_select_probe.py --seed 7 [--blocks 16,32,64] [--ops 30]

One layer call of `sparse_paged_decode` (`ray_tpu/ops/paged_attention.py`) at
24 rows x 464 table entries of 64 tokens over pools of 12288 pages, 22 rows of
16k-28k keys, one of 297 and a free slot, 2048 kept: the kernel
`sparse_decode_scores` beside `index_scores` over `index_keys` (the copy it
does without), `top_k_places` beside `lax.top_k` and a look-up of the page ids
(the sort they do without), and the whole function beside the same with those
two in their places. It also says whether the two agree: the same scores to
rounding, the same set of positions a row, the same output. Every time is
device time from a trace of `--reps` calls (the programs' own, without the
host's dispatch); with `--ops` the two whole functions' operations are
listed, ms an event. `--blocks`: the kernel at these many pages a block
(the first is what the whole function then runs with). Prints one JSON line.
"""

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LAYERS, PAGES, PAGE, KV_HEADS, HEAD_DIM = 4, 12288, 64, 4, 128
ROWS, MAX_PAGES, INDEX_HEADS, INDEX_DIM, TOPK, HEADS = 24, 464, 16, 64, 2048, 32
LOW, HIGH = 16384, 28672        # the long rows' keys


def build(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(seed)
    lengths = rng.integers(LOW, HIGH + 1, ROWS)
    lengths[rng.integers(0, ROWS // 2)] = LOW // 55
    lengths[ROWS // 2 + rng.integers(0, ROWS // 2)] = 0
    free = rng.permutation(np.arange(1, PAGES))
    table, at = np.zeros((ROWS, MAX_PAGES), np.int32), 0
    for b, n in enumerate(-(-lengths // PAGE)):
        table[b, :n] = free[at:at + n]
        at += n
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = pa.index_pack(PAGE, INDEX_DIM)
    normal = lambda k, shape: jax.jit(
        lambda k: jax.random.normal(k, shape, jnp.bfloat16))(k)
    cache = pa.PagedKVCache(
        k_pages=normal(keys[0], (LAYERS, PAGES, PAGE, KV_HEADS, HEAD_DIM)),
        v_pages=normal(keys[1], (LAYERS, PAGES, PAGE, KV_HEADS, HEAD_DIM)),
        idx_pages=normal(keys[2], (LAYERS, PAGES, PAGE // r, r * INDEX_DIM)),
        block_tables=jnp.asarray(table),
        lengths=jnp.asarray(lengths, jnp.int32))
    q = normal(keys[3], (ROWS, HEADS, HEAD_DIM))
    qi = normal(keys[4], (ROWS, INDEX_HEADS, INDEX_DIM))
    wi = normal(keys[5], (ROWS, INDEX_HEADS))
    return cache, q, qi, wi, lengths


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--blocks", default="16")
    ap.add_argument("--ops", type=int, default=0,
                    help="list the whole functions' operations, this many")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, for a run off the chip")
    args = ap.parse_args()
    if args.rehearse:
        globals().update(PAGES=96, PAGE=8, HEAD_DIM=16, ROWS=4, MAX_PAGES=40,
                         INDEX_HEADS=2, INDEX_DIM=8, TOPK=16, HEADS=8,
                         LOW=100, HIGH=320)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa
    cache, q, qi, wi, lengths = build(args.seed)
    tb = cache.block_tables
    s_max = MAX_PAGES * PAGE

    def copied_scores(qi, wi, cache, layer):
        ki = pa.index_keys(cache, layer, tb)
        s = pa.index_scores(qi[:, None], wi[:, None], ki)[:, 0]
        return jnp.where(jnp.arange(s_max)[None] < cache.lengths[:, None], s,
                         -jnp.inf)

    def sorted_places(scores):
        """What `top_k_places` took the place of: a full sort, and the page
        ids by a one-hot contraction (in base-128 digits, which bf16 holds)."""
        top, sel = jax.lax.top_k(scores, TOPK)
        one_hot = ((sel // PAGE)[:, :, None] == jnp.arange(MAX_PAGES)[None, None]
                   ).astype(jnp.bfloat16)
        digits = jnp.stack([(tb >> shift) & 127 for shift in (0, 7, 14, 21)]
                           ).astype(jnp.bfloat16)
        got = jnp.einsum("bkp,dbp->dbk", one_hot, digits,
                         preferred_element_type=jnp.float32).astype(jnp.int32)
        return (got[0] | (got[1] << 7) | (got[2] << 14) | (got[3] << 21),
                sel % PAGE, top > -jnp.inf)

    def valued_places(scores):
        return pa.top_k_places(scores, tb, TOPK, PAGE)

    def attend(q, cache, layer, places):
        page_ids, offsets, chosen = places
        k_sel = cache.k_pages[layer, page_ids, offsets]
        v_sel = cache.v_pages[layer, page_ids, offsets]
        qg = q.reshape(ROWS, KV_HEADS, HEADS // KV_HEADS, HEAD_DIM)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_sel,
                       preferred_element_type=jnp.float32) / math.sqrt(HEAD_DIM)
        s = jnp.where(chosen[:, None, None, :], s, pa._NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgs,bskd->bkgd", p.astype(v_sel.dtype),
                          v_sel).reshape(ROWS, HEADS, HEAD_DIM)

    def timed(fn, *operands, ops=None):
        """Device ms a call, from a trace of `reps` calls, a layer in turn
        (every layer's program compiled before): the programs' own time, so
        the host's dispatch is not in it. `ops`: a dict that takes the
        trace's operations, [events, ms an event]."""
        from perfbench import trace_reduce
        jitted = jax.jit(fn, static_argnums=len(operands))
        out = jax.block_until_ready([jitted(*operands, l)
                                     for l in range(LAYERS)])
        where = os.path.join(ROOT, "chiprun_out", "select_probe",
                             str(len(os.listdir(top)) if os.path.isdir(top)
                                 else 0))
        with jax.profiler.trace(where):
            jax.block_until_ready([jitted(*operands, i % LAYERS)
                                   for i in range(args.reps)])
        table = trace_reduce.reduce_file(trace_reduce.find_xplane(where))
        if ops is not None:
            ops.update({k: [c, round(sec / c * 1e3, 4)] for k, (c, sec)
                        in list(table["ops"].items())[:args.ops]})
        # (the CPU's trace, a rehearsal's, has no line of programs)
        events = sum(c for c, _ in table["modules"].values())
        assert events in (0, args.reps), table["modules"]
        seconds = (sum(sec for _, sec in table["modules"].values())
                   if events else table["busy_s"])
        return seconds / args.reps * 1e3, out[0]

    top = os.path.join(ROOT, "chiprun_out", "select_probe")
    ms, line = {}, {"device": jax.devices()[0].device_kind, "seed": args.seed,
                    "keys_held": int(lengths.sum())}
    ms["copied_scores"], was = timed(copied_scores, qi, wi, cache)
    for ppb in (int(x) for x in args.blocks.split(",")):
        pa._INDEX_PAGES_PER_BLOCK = ppb
        ms[f"sparse_decode_scores.{ppb}"], now = timed(
            lambda qi, wi, cache, layer: pa.sparse_decode_scores(
                qi, wi, cache, layer, cache.lengths), qi, wi, cache)
        both = np.isfinite(np.asarray(was))
        assert (both == np.isfinite(np.asarray(now))).all()
        line[f"scores_max_abs_diff.{ppb}"] = float(
            np.abs(np.asarray(was)[both] - np.asarray(now)[both]).max())
    pa._INDEX_PAGES_PER_BLOCK = int(args.blocks.split(",")[0])
    ms["sorted_places"], old = timed(lambda s, layer: sorted_places(s), was)
    ms["valued_places"], new = timed(lambda s, layer: valued_places(s), was)
    place = lambda p: np.sort(np.where(
        np.asarray(p[2]), np.asarray(p[0]) * PAGE + np.asarray(p[1]), -1), -1)
    line["same_set_every_row"] = bool((place(old) == place(new)).all())
    ops = {"decode_sorted": {}, "decode_valued": {}}
    ms["decode_sorted"], out_old = timed(
        lambda q, qi, wi, cache, layer: attend(
            q, cache, layer, sorted_places(copied_scores(qi, wi, cache, layer))),
        q, qi, wi, cache, ops=ops["decode_sorted"])
    ms["decode_valued"], out_new = timed(
        lambda q, qi, wi, cache, layer: pa.sparse_paged_decode(
            q, qi, wi, cache, layer, cache.lengths, TOPK), q, qi, wi, cache,
        ops=ops["decode_valued"])
    if args.ops:
        line["ops_events_ms_an_event"] = ops
    held = lengths > 0          # a free slot attends to whatever slot 0 names
    line["output_max_abs_diff"] = float(np.abs(
        np.asarray(out_old, np.float32)[held]
        - np.asarray(out_new, np.float32)[held]).max())
    line["ms_a_call"] = {k: round(v, 4) for k, v in ms.items()}

    print(json.dumps(line))


if __name__ == "__main__":
    main()
