"""Sharding-rule engine: param-tree path patterns → PartitionSpec.

Reference contrast: torch DDP/FSDP wrap modules imperatively
(python/ray/train/torch). The TPU-native equivalent is declarative: a table
of (path regex → PartitionSpec) applied over the param pytree, producing
NamedShardings that pjit consumes; XLA then emits all-gathers/reduce-scatters
(FSDP) or keeps weights resident (TP) as the specs dictate.
"""

import math
import re
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_str(path) -> str:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "idx"):
            keys.append(str(p.idx))
        else:
            keys.append(str(p))
    return "/".join(keys)


def tree_paths(tree):
    """Flatten a pytree into ("a/b/c", leaf) pairs."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_path_str(path), leaf) for path, leaf in flat]


class ShardingRules:
    """Ordered (regex, PartitionSpec) table; first match wins."""

    def __init__(self, rules: Sequence[Tuple[str, P]], default: P = P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default = default

    def spec_for(self, path: str, leaf=None) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return _clip_spec(spec, leaf)
        return _clip_spec(self.default, leaf)

    def tree_specs(self, tree):
        """PartitionSpec pytree matching `tree`'s structure."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = [self.spec_for(_path_str(path), leaf) for path, leaf in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    def tree_shardings(self, tree, mesh: Mesh):
        specs = self.tree_specs(tree)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, _filter_axes(s, mesh)), specs,
            is_leaf=lambda x: isinstance(x, P))


def _clip_spec(spec: P, leaf) -> P:
    """Trim a spec to the leaf's rank (rules can be written for the widest case)."""
    if leaf is None or not hasattr(leaf, "ndim"):
        return spec
    return P(*tuple(spec)[: leaf.ndim]) if len(tuple(spec)) > leaf.ndim else spec


def _filter_axes(spec: P, mesh: Mesh) -> P:
    """Drop mesh axes the current mesh doesn't have (rules stay portable
    between e.g. a tp-only mesh and a dp×fsdp×tp mesh)."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*[keep(e) for e in tuple(spec)])


def shard_tree(tree, mesh: Mesh, rules: "ShardingRules"):
    """device_put the pytree according to the rules (host → sharded HBM)."""
    shardings = rules.tree_shardings(tree, mesh)
    return jax.device_put(tree, shardings)


# ---------------------------------------------------------------------------
# Canonical transformer rules (llama-family param tree, see models/llama.py).
# fsdp shards the large dimension of every matrix; tp shards heads/ffn.
# ---------------------------------------------------------------------------

def llama_rules() -> ShardingRules:
    return ShardingRules([
        (r"embed/embedding", P(("fsdp",), ("tp",))),          # [vocab, d]
        # the lightning indexer (one key head, 2M parameters a layer) and
        # the per-head q/k norm scales are replicated: every shard makes the
        # same selection. Before the wq/wk rule, which its names would match
        (r"attn/indexer/", P()),
        (r"attn/(q_norm|k_norm)/scale", P()),
        (r"(wq|wk|wv)/kernel", P(("fsdp",), ("tp",))),         # [d, heads*hd]
        (r"wo/kernel", P(("tp",), ("fsdp",))),                 # [heads*hd, d]
        (r"(w_gate|w_up)/kernel", P(("fsdp",), ("tp",))),      # [d, ffn]
        (r"w_down/kernel", P(("tp",), ("fsdp",))),             # [ffn, d]
        (r"lm_head/kernel", P(("fsdp",), ("tp",))),            # [d, vocab]
        # MoE expert banks (models/moe.py): leading E dim over `ep`, inner
        # dims shard like the dense FFN; the tiny router stays replicated
        # so every shard routes identically
        (r"moe/router/kernel", P()),                           # [d, E]
        (r"moe/w_(gate|up)$", P(("ep",), ("fsdp",), ("tp",))),  # [E, d, ffn]
        (r"moe/w_down$", P(("ep",), ("tp",), ("fsdp",))),       # [E, ffn, d]
        (r"(norm|ln)", P()),                                   # replicated
    ], default=P())


def batch_spec(extra_seq_axis: bool = False) -> P:
    """Activations: batch over (dp, fsdp); optionally sequence over sp."""
    if extra_seq_axis:
        return P(("dp", "fsdp"), ("sp",))
    return P(("dp", "fsdp"))


def data_sharding(mesh: Mesh, extra_seq_axis: bool = False) -> NamedSharding:
    return NamedSharding(mesh, _filter_axes(batch_spec(extra_seq_axis), mesh))


def rows_gathered_once(loss: Callable, mesh: Mesh, w_spec: P, batch: int):
    """`loss(hidden[B, ...], w[D, V], labels[B, ...]) -> scalar mean over B`,
    run per shard of the mesh's data axes (`batch_spec`), with `w`'s rows
    all-gathered ONCE on the way in over those of them that shard the rows
    (fsdp).

    Left to the partitioner, a loss that loops over chunks carries `w`'s
    SHARD through the loop and puts the collectives where the dots are,
    inside every trip (`ops.losses.chunked_cross_entropy` has the HLO names).
    Under a shard_map they are where they are written: one `all_gather`
    before the loop (XLA moves the cast to the compute dtype in front of it,
    so the wire carries bf16) and its transpose, one `psum_scatter` after it
    of the gradient summed over the loop on the chip in `w`'s own dtype; the
    hidden state's cotangent leaves sharded over the batch, as the forward
    is. Each shard takes the mean of its own rows and the shards' means are
    averaged, so only the order of summation differs from the bare loss.

    Returns `loss` itself where there is nothing to gather over: no axis of
    `w_spec`'s rows is a data axis of this mesh, or `batch` does not split."""
    rows = tuple(w_spec)[0] if tuple(w_spec) else None
    rows = (rows,) if isinstance(rows, str) else tuple(rows or ())
    data = tuple(a for a in batch_spec()[0] if mesh.shape.get(a, 1) > 1)
    axes = tuple(a for a in rows if a in data)
    if not axes or batch % math.prod(mesh.shape[a] for a in data):
        return loss

    def per_shard(hidden, w, labels):
        w = jax.lax.all_gather(w, axes, axis=0, tiled=True)
        return jax.lax.pmean(loss(hidden, w, labels), data)

    # check_vma off: the loss's scan starts its sums from constants, which
    # the check reads as not varying over `data` where the body's sums do
    return jax.shard_map(per_shard, mesh=mesh,
                         in_specs=(P(data), P(axes), P(data)), out_specs=P(),
                         axis_names=set(data), check_vma=False)
