"""How the grouped expert product hands its rows and matrices to megablox's
`gmm` (ISSUE 43): the tiles `_gmm_tiling` gives the served shapes, a share
bank's groups laid on row-tile edges by `grouped_experts`, the kernel under
those tiles and sizes against `jax.lax.ragged_dot` (interpret mode: the CPU),
and how many tiles of the experts' matrices a product fetches, counted from
the kernel's own grid and group metadata.

The count is the pipeline's rule applied to `gmm`'s grid: (n tiles, visits,
k tiles) with k innermost, the matrix's block index (group, k tile, n tile),
and a block fetched whenever that index differs from the step before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from ray_tpu.models import moe
from ray_tpu.models.moe import _gmm_tiling, grouped_experts

# (rows, k, n) -> tiles, bf16: what the rule gave before ISSUE 43 and gives
# still (Keye's matrices fit one tile: the control). Decode's products keep
# their rows, so their kernels keep their names (`gmm_bf16_384_...`).
SERVED = {
    "keye chunk gate/up": ((4096, 2048, 768), (64, 2048, 768)),
    "keye decode gate/up": ((192, 2048, 768), (64, 2048, 768)),
    "keye chunk down": ((4096, 768, 2048), (64, 768, 2048)),
    "keye decode down": ((192, 768, 2048), (64, 768, 2048)),
    "command chunk on edges": ((10240, 4096, 4096), (64, 1024, 2048)),
    "command decode": ((384, 4096, 4096), (64, 1024, 2048)),
    "command bucket of 16": ((128, 4096, 4096), (64, 1024, 2048)),
    "solar chunk gate/up": ((8192, 4096, 1280), (64, 1024, 1280)),
    "solar decode gate/up": ((128, 4096, 1280), (64, 1024, 1280)),
    "solar chunk down": ((8192, 1280, 4096), (64, 1280, 1024)),
    "solar decode down": ((128, 1280, 4096), (64, 1280, 1024)),
}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_tiling_of_the_served_shapes(case):
    (m, k, n), want = SERVED[case]
    got = _gmm_tiling(m, k, n, 2)
    assert got == want
    tm, tk, tn = got
    assert m % tm == 0 and k % tk == 0 and n % tn == 0
    assert tk * tn * 2 <= moe._GMM_RHS_TILE_BYTES


@pytest.mark.parametrize("shape", [(100, 256, 256),      # rows not of 8
                                   (64, 2120, 1000)])    # no side halves
def test_no_tiling_where_nothing_fits(shape):
    assert _gmm_tiling(*shape, 2) is None


def _routing(seed, tokens=1024, n_experts=128, held=16, top_k=8,
             all_held=False):
    """Gates, local expert indices (`held`: another chip's) and the held
    mask of `tokens` tokens that keep `top_k` of `n_experts` by seeded
    scores, on a bank that holds the first `held`."""
    vals, idx = jax.lax.top_k(jax.random.uniform(
        jax.random.PRNGKey(seed), (tokens, n_experts)), top_k)
    if all_held:
        idx = idx % held
    mine = idx < held
    return vals / vals.sum(-1, keepdims=True), jnp.where(mine, idx, held), mine


def _products(monkeypatch, local, mine, held, edges):
    """(rows, sizes, tm) of each product `grouped_experts` makes for this
    routing, the experts' matrices too large for one tile (`edges`) or not."""
    calls = []

    def spy(lhs, rhs, sizes, tm=None):
        calls.append((lhs.shape[0], np.asarray(sizes), tm))
        return jnp.zeros((lhs.shape[0], rhs.shape[-1]), lhs.dtype)

    monkeypatch.setattr(moe, "_grouped_dot", spy)
    monkeypatch.setattr(moe, "_GMM_RHS_TILE_BYTES", 8 if edges else 1 << 20)
    w = jnp.zeros((held, 4, 4), jnp.float32)
    grouped_experts(jnp.zeros((local.shape[0], 4)), jnp.ones(local.shape),
                    local, w, w, w, held=mine)
    assert len(calls) == 3 and all(c[0] == calls[0][0] for c in calls)
    return calls[0]


def _matrix_tile_fetches(sizes, m, k, n, tiling):
    """Tiles of the experts' matrices `gmm` fetches for one product, and its
    (row tile, group) visits."""
    tm, tk, tn = tiling
    (_, group_ids, _), visits = make_group_metadata(
        group_sizes=jnp.asarray(sizes), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    group_ids, fetches, last = np.asarray(group_ids), 0, None
    for n_i in range(n // tn):
        for v in range(int(visits)):
            for k_i in range(k // tk):
                block = (group_ids[v], k_i, n_i)
                fetches += block != last
                last = block
    return fetches, int(visits)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_chunks_product_fetches_each_reached_matrix_once(monkeypatch, seed):
    """Command A+'s chunk: 16 of 128 experts held, 1024 tokens x 8, matrices
    of 4096 x 4096 in eight tiles of 1024 x 2048."""
    k = n = 4096
    _, local, mine = _routing(seed)
    tk, tn = _gmm_tiling(8192, k, n, 2)[1:]
    tiles = (k // tk) * (n // tn)
    assert tiles == 8
    # as it was: some 64 sorted rows a group start anywhere, so nearly every
    # group straddles a row-tile edge, and every visit streams the matrix
    m, sizes, tm = _products(monkeypatch, local, mine, 16, edges=False)
    assert (m, tm) == (8192, None)
    reached = int((sizes > 0).sum())
    fetched, visits = _matrix_tile_fetches(sizes, m, k, n, (64, tk, tn))
    assert fetched == visits * tiles
    assert 1.7 * reached <= visits <= 2.2 * reached
    # on edges: a group of up to 128 rows is one visit
    m, padded, tm = _products(monkeypatch, local, mine, 16, edges=True)
    assert (m, tm) == (8192 + 16 * 128, 128)
    assert (padded % 128 == 0).all() and (padded >= sizes).all()
    assert (padded - sizes < 128).all() and ((padded > 0) == (sizes > 0)).all()
    fetched, visits = _matrix_tile_fetches(padded, m, k, n, (tm, tk, tn))
    assert visits == (padded // 128).sum() <= reached + 1
    assert fetched == visits * tiles


def test_a_decode_steps_rows_are_left_as_they_lie(monkeypatch):
    """48 rows x 8: the held pairs lie in one row tile, a visit a group, and
    the product keeps its 384 rows (its kernel's name in a trace)."""
    _, local, mine = _routing(3, tokens=48)
    m, sizes, tm = _products(monkeypatch, local, mine, 16, edges=True)
    assert (m, tm) == (384, None)
    _, visits = _matrix_tile_fetches(sizes, m, 4096, 4096, (64, 1024, 2048))
    assert visits == (sizes > 0).sum()


def test_under_two_tiles_a_group_the_rows_are_left_as_they_lie(monkeypatch):
    """Solar's chunk, 40 of 320 experts held on 8192 rows: the padding would
    be five eighths of the rows; and Command A+'s chunk of 256 tokens."""
    for tokens, n_experts, held in ((1024, 320, 40), (256, 128, 16)):
        _, local, mine = _routing(5, tokens, n_experts, held)
        m, _, tm = _products(monkeypatch, local, mine, held, edges=True)
        assert (m, tm) == (tokens * 8, None)
    _, local, mine = _routing(5, 512, 128, 16)
    assert _products(monkeypatch, local, mine, 16, edges=True)[::2] == (
        4096 + 16 * 128, 128)


# on edges of 128: a group of more than a tile, an empty group, a group of
# one row, and rows past the groups' sum (another chip's pairs, no one's)
@pytest.mark.parametrize("sizes", [[256, 0, 128, 128], [128, 128, 0, 128],
                                   [0, 0, 0, 384], [128, 256, 128, 128]])
def test_gmm_under_the_tiling_equals_ragged_dot(monkeypatch, sizes):
    m, k, n, budget = 768, 512, 256, 256 << 10      # float32: 512 KiB a matrix
    monkeypatch.setattr(moe, "_GMM_RHS_TILE_BYTES", budget)
    tiling = (128,) + _gmm_tiling(m, k, n, 4)[1:]
    assert tiling == (128, 256, 256)                 # k in two tiles
    kl, kr = jax.random.split(jax.random.PRNGKey(sum(sizes)))
    lhs = jax.random.normal(kl, (m, k), jnp.float32)
    rhs = jax.random.normal(kr, (len(sizes), k, n), jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    got = gmm(lhs, rhs, sz, preferred_element_type=jnp.float32,
              tiling=tiling, interpret=True)
    rows = sum(sizes)
    assert rows < m
    np.testing.assert_allclose(got[:rows], jax.lax.ragged_dot(lhs, rhs, sz)[
        :rows], rtol=1e-5, atol=1e-4)
    fetched, visits = _matrix_tile_fetches(np.asarray(sizes), m, k, n, tiling)
    assert visits == rows // 128 and fetched == 2 * visits


@pytest.mark.parametrize("seed,tokens,all_held", [
    (0, 256, False),      # an eighth of the pairs held
    (1, 256, True),       # the worst routing: every pair on a held expert
    (2, 128, False),      # rows of exactly two tiles a group
    (3, 64, False)])      # fewer: left as they lie
def test_groups_on_tile_edges_change_no_result(monkeypatch, seed, tokens,
                                               all_held):
    held, d, f = 4, 16, 24
    vals, local, mine = _routing(seed, tokens, 32, held, all_held=all_held)
    assert bool(mine.all()) == all_held
    kx, *kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (tokens, d), jnp.float32)
    w = [jax.random.normal(key, shape, jnp.float32) for key, shape in zip(
        kw, [(held, d, f), (held, d, f), (held, f, d)])]
    want = grouped_experts(x, vals, local, *w, held=mine)
    moe._TILINGS_TAKEN.clear()
    monkeypatch.setattr(moe, "_GMM_RHS_TILE_BYTES", d * f * 4 - 1)
    got = grouped_experts(x, vals, local, *w, held=mine)
    np.testing.assert_array_equal(got, want)
    rows = tokens * 8 + (held * 128 if tokens >= 128 else 0)
    assert set(moe.gmm_tilings()) == {f"{rows} x {d} x {f}",
                                      f"{rows} x {f} x {d}"}


def test_a_whole_bank_is_left_as_it_lies(monkeypatch):
    """Keye's form (no `held`): every row is some group's, nothing to spare."""
    vals, idx, _ = _routing(4, 256, 4, 4, top_k=2)
    seen = []
    monkeypatch.setattr(moe, "_GMM_RHS_TILE_BYTES", 8)
    monkeypatch.setattr(moe, "_grouped_dot", lambda lhs, rhs, sizes, tm=None: (
        seen.append((lhs.shape[0], tm)),
        jnp.zeros((lhs.shape[0], rhs.shape[-1])))[1])
    w = jnp.zeros((4, 4, 4), jnp.float32)
    grouped_experts(jnp.zeros((256, 4)), vals, idx, w, w, w)
    assert seen == [(512, None)] * 3


def test_the_tiling_taken_is_on_record():
    """On the CPU the product is `ragged_dot`: the record says None."""
    x = jnp.ones((16, 8), jnp.float32)
    w = jnp.ones((3, 8, 24), jnp.float32)
    moe._grouped_dot(x, w, jnp.asarray([4, 0, 5], jnp.int32))
    assert moe.gmm_tilings()["16 x 8 x 24"] is None
    assert moe.gmm_tilings() is not moe.gmm_tilings()       # a copy
