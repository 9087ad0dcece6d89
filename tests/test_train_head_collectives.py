"""The head's weights cross the chips once a step (PR 34): under an fsdp mesh
`make_lm_train_step` gathers `lm_head` once before the loss loops and
reduce-scatters its f32 gradient once after them; the loops hold no
collective over the head or the logits. The parent's formula (the loss left
to the partitioner) is kept here as the counter-example."""

import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import Llama, LlamaConfig
from ray_tpu.ops.losses import chunked_cross_entropy, cross_entropy
from ray_tpu.parallel import sharding
from ray_tpu.parallel.mesh import local_cpu_mesh, make_mesh
from ray_tpu.train.lm import make_lm_train_step


def _parent_form():
    """`rows_gathered_once` as the parent had it: not there. The loss reaches
    the partitioner bare, which is the program of every PR before this."""
    return mock.patch.object(sharding, "rows_gathered_once",
                             lambda loss, *_: loss)


# -- (a) the step compiled for the v5e's 2x2 mesh -----------------------------
# One process may hold the TPU's library: the topology is described in a
# fixture, in this file only (on-chip-measurement guide, section 2).

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
D, V = 4096, 32000                       # the cell's head
VOCAB = re.compile(r"\b%d\]" % V)        # the head, its gradient, the logits


def _computations(hlo):
    """{name: [instruction lines]} of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.startswith("}"):
            name = None
        elif line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[name] = []
        elif name:
            comps[name].append(line)
    return comps


def _instructions(lines):
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
        if m:
            yield m.group(1), m.group(2), line


def _reachable(comps, root):
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for m in re.finditer(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
                todo.append(m.group(1))
    return seen


def head_collectives(hlo):
    """What a compiled step does with the head across chips: the collectives
    on an array with the vocabulary in it, as (opcode, element type, dims,
    inside a `while`) tuples.

    An asynchronous collective is three fusions (its start, the compute it
    hides behind, its done) that each hold the instruction with the same
    type and `op_name`: it is one. The compiler's `all-reduce-scatter` is a
    `kCustom` fusion over an `all-reduce`: found as the all-reduce inside."""
    comps = _computations(hlo)
    in_loops = set()
    for lines in comps.values():
        for _, op, line in _instructions(lines):
            if op == "while":
                body = re.search(r"body=%?([\w.\-]+)", line).group(1)
                in_loops |= _reachable(comps, body)
    found = set()
    for name, lines in comps.items():
        for ty, op, line in _instructions(lines):
            m = re.match(r"\(?(\w+)\[([\d,]+)\]", ty)
            if op.startswith(COLLECTIVES) and m and VOCAB.search(ty):
                source = re.search(r'op_name="([^"]*)"', line)
                found.add((op, m.group(1), m.group(2), name in in_loops,
                           source and source.group(1)))
    return sorted(f[:4] for f in found)


def _of_the_head(found):
    """(gathers to [D, V], reductions of a [D, V] array) among `found`: a
    reduce-scatter's result is its shard, some rows of V."""
    gathers = [f for f in found if f[0] == "all-gather"
               and f[2] == f"{D},{V}"]
    reductions = [f for f in found if (f[0], f[2]) == ("all-reduce", f"{D},{V}")
                  or f[0] == "reduce-scatter"
                  and re.fullmatch(r"\d+,%d" % V, f[2])]
    return gathers, reductions


@pytest.fixture(scope="module")
def compiled_for_v5e():
    """The cell's step at 1 layer, compiled for `v5e:2x2` under `{"fsdp": 4}`
    with nothing materialised: `new` as the repo builds it, `parent` with the
    loss left to the partitioner. HLO text of each."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = make_mesh({"fsdp": 4}, devices=topo.devices)
    cfg = LlamaConfig.llama_8b(
        max_seq_len=4096, param_dtype=jnp.float32, remat=False,
        attn_impl="flash", vocab_size=V, d_model=D, n_layers=1, n_heads=32,
        n_kv_heads=8, head_dim=128, ffn_dim=14336, rope_theta=1e6)
    real_jit = jax.jit

    def shapes_only(fn, **kw):
        """jax.jit for the step; the initialisers give shapes with their
        `out_shardings` (a described device holds no array)."""
        if fn.__name__ == "step":
            return real_jit(fn, **kw)
        return lambda *a: jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(fn, *a), kw["out_shardings"])

    def build():
        with mock.patch.object(jax, "jit", shapes_only):
            params, opt_state, step = make_lm_train_step(
                cfg, optax.adamw(1e-4), jax.random.PRNGKey(0), mesh=mesh)
        tokens = jax.ShapeDtypeStruct((4, 4097), jnp.int32,
                                      sharding=sharding.data_sharding(mesh))
        with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                jax.set_mesh(mesh):
            return step.lower(params, opt_state, tokens).compile().as_text()

    new = build()
    with _parent_form():
        parent = build()
    return {"new": new, "parent": parent}


def test_compiled_for_the_v5e_the_loss_loops_hold_no_head_collective(
        compiled_for_v5e):
    found = head_collectives(compiled_for_v5e["new"])
    assert [f for f in found if f[3]] == []
    gathers, reductions = _of_the_head(found)
    assert len(gathers) == 1, found
    assert len(reductions) == 1 and reductions[0][1] == "f32", found
    # and nothing else with the vocabulary in it crosses the chips: no
    # gather of the logits' cotangents
    assert len(found) == 2, found


def test_the_parents_form_fails_the_same_assertions(compiled_for_v5e):
    """Sixteen gathers and eight reduce-scatters a step show as three
    instructions in two loop bodies, and one more of the logits'
    cotangents."""
    found = head_collectives(compiled_for_v5e["parent"])
    gathers, reductions = _of_the_head(found)
    assert len(gathers) == 2 and all(f[3] for f in gathers), found
    assert len(reductions) == 1 and reductions[0][3], found
    assert reductions[0][1] == "f32"        # what the change has to keep
    assert ("all-gather", "bf16", f"4,512,{V}", True) in found


# -- (b) on four CPU devices, against the plain cross entropy -----------------

TINY = dict(dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=32)


def _leaves(tree):
    return {name: leaf for name, leaf in sharding.tree_paths(tree)}


@pytest.fixture(scope="module")
def tiny_grads():
    """Loss and gradients of one tiny model on one batch, three ways: the
    new step's `loss_fn` under a 4-device fsdp mesh, the parent's form under
    the same mesh, and `cross_entropy` on full logits on one device."""
    cfg = LlamaConfig.tiny(**TINY)
    model = Llama(cfg)
    mesh = local_cpu_mesh(4, {"fsdp": 4})
    key = jax.random.PRNGKey(3)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 33), dtype=np.int32)

    def reference(params, tokens):
        logits, _ = model.apply(params, tokens[:, :-1])
        return cross_entropy(logits, tokens[:, 1:])[0]

    def through_the_step(**kw):
        """(loss, grads, params) by the repo's own step: sgd at rate 1 turns
        the update into the gradient, old minus new."""
        params, opt_state, step = make_lm_train_step(
            cfg, optax.sgd(1.0), key, loss_chunk=8, **kw)
        before = jax.tree_util.tree_map(np.asarray, params)
        placed = jax.device_put(tokens, sharding.data_sharding(kw["mesh"])) \
            if kw else tokens
        after, _, loss = step(params, opt_state, placed)
        grads = jax.tree_util.tree_map(lambda b, a: b - a, before, after)
        return float(loss), grads, after

    with jax.set_mesh(mesh):
        new = through_the_step(mesh=mesh)
        with _parent_form():
            parent = through_the_step(mesh=mesh)
    plain = through_the_step()
    params = jax.tree_util.tree_map(jnp.asarray, model.init(
        key, jnp.zeros((2, 8), jnp.int32)))
    want = jax.value_and_grad(reference)(params, tokens)
    return {"new": new, "parent": parent, "plain": plain,
            "want": (float(want[0]), want[1])}


@pytest.mark.parametrize("form", ["new", "parent", "plain"])
def test_loss_equals_the_plain_cross_entropy(tiny_grads, form):
    assert abs(tiny_grads[form][0] - tiny_grads["want"][0]) < 1e-5


_TINY_LEAVES = sorted(_leaves(jax.eval_shape(
    lambda: Llama(LlamaConfig.tiny(**TINY)).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32)))))


@pytest.mark.parametrize("leaf", _TINY_LEAVES)
def test_every_gradient_leaf_equals_the_plain_cross_entropys(tiny_grads, leaf):
    want = np.asarray(_leaves(tiny_grads["want"][1])[leaf])
    got = np.asarray(_leaves(tiny_grads["new"][1])[leaf])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert np.abs(want).max() > 1e-5        # a gradient, not a zero


def test_the_heads_gradient_is_f32_and_sharded_as_the_parameter(tiny_grads):
    """The updated head leaves the step f32 and split by rows over fsdp,
    as it went in."""
    head = tiny_grads["new"][2]["params"]["lm_head"]["kernel"]
    assert head.dtype == jnp.float32
    rows = head.shape[0] // 4
    assert {s.data.shape for s in head.addressable_shards} == {
        (rows, head.shape[1])}
    assert head.sharding.spec[0] in ("fsdp", ("fsdp",))


@pytest.mark.parametrize("mesh_axes,batch,wrapped", [
    ({"fsdp": 4}, 8, True), ({"fsdp": 2, "tp": 2}, 4, True),
    ({"dp": 2, "fsdp": 2}, 4, True),
    ({"fsdp": 4}, 6, False),      # the batch does not split over fsdp
    ({"dp": 4}, 8, False),        # nothing shards the head's rows
    ({"tp": 4}, 8, False)])
def test_the_wrap_depends_on_what_the_mesh_shards(mesh_axes, batch, wrapped):
    """`rows_gathered_once` hands the loss back untouched where the head's
    rows are on no data axis of the mesh or the batch does not split; where
    it wraps, loss and gradients are the unwrapped loss's."""
    mesh = local_cpu_mesh(4, mesh_axes)
    spec = sharding.llama_rules().tree_shardings(
        {"lm_head": {"kernel": jax.ShapeDtypeStruct((16, 64), jnp.float32)}},
        mesh)["lm_head"]["kernel"].spec

    def loss(h, w, y):
        return chunked_cross_entropy(h, w, y, chunk_size=4)[0]

    got = sharding.rows_gathered_once(loss, mesh, spec, batch)
    assert (got is not loss) == wrapped
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(batch, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 64, (batch, 8)), jnp.int32)
    want = jax.value_and_grad(loss, argnums=(0, 1))(h, w, y)
    with jax.set_mesh(mesh):
        have = jax.jit(jax.value_and_grad(got, argnums=(0, 1)))(h, w, y)
    np.testing.assert_allclose(have[0], want[0], atol=1e-6)
    for a, b in zip(have[1], want[1]):
        np.testing.assert_allclose(a, b, atol=1e-6)


# -- (c) one chip: the parent's program, text for text ------------------------

def test_without_a_mesh_the_step_lowers_to_the_parents_text():
    cfg = LlamaConfig.tiny(**TINY)
    model, optimizer = Llama(cfg), optax.adamw(1e-4)
    params, opt_state, new_step = make_lm_train_step(
        cfg, optimizer, jax.random.PRNGKey(0), loss_chunk=8)

    def loss_fn(params, tokens):                     # the parent's, verbatim
        hidden, _ = model.apply(params, tokens[:, :-1], return_hidden=True)
        w_head = params["params"]["lm_head"]["kernel"]
        loss, _ = chunked_cross_entropy(
            hidden, w_head, tokens[:, 1:],
            chunk_size=min(8, tokens.shape[1] - 1))
        return loss

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    tokens = jnp.zeros((2, 33), jnp.int32)
    new = new_step.lower(params, opt_state, tokens).as_text()
    old = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens).as_text()
    assert new == old
    assert "sharding_constraint" not in new and "shard_map" not in new
    assert "all_gather" not in new and "all-gather" not in new
