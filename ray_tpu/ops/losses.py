"""Loss and advantage math shared by train/rllib (SURVEY.md §2 models/ops).

All functions are pure jnp, f32 accumulation, scan-based where the reference
uses Python loops over timesteps (GAE, V-trace) — reference: rllib's
postprocessing/vtrace torch code; here the recurrences are `lax.scan` so they
live inside jit.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def cross_entropy(
    logits: jax.Array,          # [..., V]
    labels: jax.Array,          # [...] int
    mask: Optional[jax.Array] = None,  # [...] 0/1 or bool
    z_loss: float = 0.0,
    label_smoothing: float = 0.0,
):
    """Mean token cross-entropy with optional z-loss (logsumexp² regularizer,
    keeps bf16 logits from drifting) and label smoothing.

    Returns (loss, metrics dict with 'loss', 'z_loss', 'accuracy', 'tokens').
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logits
    if label_smoothing:
        smooth = -jnp.mean(logits, axis=-1) + lse
        nll = (1 - label_smoothing) * nll + label_smoothing * smooth
    zl = jnp.square(lse)

    if mask is None:
        mask = jnp.ones(nll.shape, jnp.float32)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    zterm = z_loss * jnp.sum(zl * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, axis=-1) == labels) * mask) / denom
    return loss + zterm, {
        "loss": loss, "z_loss": zterm, "accuracy": acc, "tokens": jnp.sum(mask)}


def chunked_cross_entropy(
    hidden: jax.Array,   # [B, T, D] final hidden states (pre-lm_head)
    w_head: jax.Array,   # [D, V] lm_head kernel
    labels: jax.Array,   # [B, T] int
    chunk_size: int = 512,
):
    """Cross-entropy fused with the lm_head, computed per sequence chunk.

    The full [B, T, V] f32 logits tensor is the single largest activation in
    LLM training (llama_1b @ B=8, T=2048: ~4 GB with softmax intermediates) —
    the classic memory wall the reference hits with torch fused CE kernels.
    Here each chunk's logits are produced, reduced, and (via jax.checkpoint)
    recomputed in the backward, so peak logits memory is B·chunk·V instead of
    B·T·V. FLOPs are unchanged; only the head matmul is recomputed once.

    Supports the dense-LM subset of `cross_entropy`: no mask / z_loss /
    label_smoothing (use `cross_entropy` on full logits for those). Returns
    (mean_loss, {"loss", "accuracy", "tokens"}).

    Across chips: `w_head` is invariant over the scan, so its gradient is
    summed over the chunks in `w_head`'s dtype in the backward scan's carry.
    This function holds no collective, and a caller whose `w_head` is
    SHARDED (fsdp) must not hand it in as it is: the partitioner then
    carries the shard through both loops and gathers it inside every trip
    (`all-gather bf16[D, V]` in `wide.region_*_spmd.sunk`, 2 x T/chunk a
    step), gathers every chip's logit cotangents of a chunk
    (`all-gather bf16[B, chunk, V]`) and reduce-scatters the f32 gradient in
    every backward trip (a `kCustom` fusion calling `all-reduce-scatter`,
    `fusion_f32_<D/n>_<V>_` on a trace's operation line): a seventh of a
    Mistral-7B step on four v5e chips. `parallel.sharding.rows_gathered_once`
    wraps the call so that the head is gathered once before the loops and
    its gradient reduced once after them; `train.lm.make_lm_train_step` does.
    """
    b, t, d = hidden.shape
    assert t % chunk_size == 0, (t, chunk_size)
    nc = t // chunk_size
    h = hidden.reshape(b, nc, chunk_size, d).swapaxes(0, 1)   # [nc, B, c, D]
    y = labels.reshape(b, nc, chunk_size).swapaxes(0, 1)      # [nc, B, c]

    @jax.checkpoint
    def body(carry, hy):
        nll_sum, acc_sum = carry
        h_c, y_c = hy
        logits = jax.lax.dot_general(
            h_c, w_head, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [B, c, V]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        label_logits = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        nll = lse - label_logits
        hits = jnp.sum(jnp.argmax(logits, -1) == y_c)
        return (nll_sum + jnp.sum(nll), acc_sum + hits), None

    (nll_sum, hits), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.int32(0)), (h, y))
    n = b * t
    loss = nll_sum / n
    return loss, {"loss": loss, "accuracy": hits / n, "tokens": n}


def gae(
    rewards: jax.Array,   # [T] or [T, B]
    values: jax.Array,    # [T+1] or [T+1, B] (bootstrap value appended)
    dones: jax.Array,     # [T] (1.0 where episode ended at step t)
    gamma: float = 0.99,
    lam: float = 0.95,
):
    """Generalized advantage estimation via reverse scan.

    Returns (advantages [T], value_targets [T])."""
    not_done = 1.0 - dones.astype(jnp.float32)
    deltas = rewards + gamma * values[1:] * not_done - values[:-1]

    def body(carry, xs):
        delta, nd = xs
        carry = delta + gamma * lam * nd * carry
        return carry, carry

    _, adv_rev = jax.lax.scan(body, jnp.zeros_like(deltas[0]),
                              (deltas[::-1], not_done[::-1]))
    adv = adv_rev[::-1]
    return adv, adv + values[:-1]


class VTraceReturns(NamedTuple):
    vs: jax.Array          # [T] v-trace value targets
    pg_advantages: jax.Array


def vtrace(
    behaviour_log_probs: jax.Array,  # [T]
    target_log_probs: jax.Array,     # [T]
    rewards: jax.Array,              # [T]
    values: jax.Array,               # [T+1] (bootstrap appended)
    dones: jax.Array,                # [T]
    gamma: float = 0.99,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
) -> VTraceReturns:
    """IMPALA V-trace (Espeholt et al. 2018): off-policy-corrected value
    targets via truncated importance weights, reverse scan form."""
    rhos = jnp.exp(target_log_probs - behaviour_log_probs)
    clipped_rhos = jnp.minimum(clip_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)
    not_done = 1.0 - dones.astype(jnp.float32)
    deltas = clipped_rhos * (rewards + gamma * values[1:] * not_done - values[:-1])

    def body(acc, xs):
        delta, c, nd = xs
        acc = delta + gamma * c * nd * acc
        return acc, acc

    _, acc_rev = jax.lax.scan(
        body, jnp.zeros_like(deltas[0]), (deltas[::-1], cs[::-1], not_done[::-1]))
    vs_minus_v = acc_rev[::-1]
    vs = vs_minus_v + values[:-1]
    vs_next = jnp.concatenate([vs[1:], values[-1:]])
    pg_adv = clipped_rhos * (rewards + gamma * vs_next * not_done - values[:-1])
    return VTraceReturns(vs=vs, pg_advantages=pg_adv)


def ppo_surrogate(
    log_probs: jax.Array,
    old_log_probs: jax.Array,
    advantages: jax.Array,
    clip: float = 0.2,
):
    """Clipped PPO policy loss (to minimize) and clip-fraction metric."""
    ratio = jnp.exp(log_probs - old_log_probs)
    unclipped = ratio * advantages
    clipped = jnp.clip(ratio, 1 - clip, 1 + clip) * advantages
    loss = -jnp.mean(jnp.minimum(unclipped, clipped))
    clip_frac = jnp.mean((jnp.abs(ratio - 1.0) > clip).astype(jnp.float32))
    return loss, clip_frac


def clipped_value_loss(values, old_values, targets, clip: float = 10.0):
    """PPO-style clipped value loss (max of clipped/unclipped SE), halved."""
    clipped = old_values + jnp.clip(values - old_values, -clip, clip)
    err = jnp.maximum(jnp.square(values - targets), jnp.square(clipped - targets))
    return 0.5 * jnp.mean(err)


def huber(x: jax.Array, delta: float = 1.0) -> jax.Array:
    """Elementwise Huber; mean-reduce at the call site (DQN TD errors)."""
    abs_x = jnp.abs(x)
    return jnp.where(abs_x <= delta, 0.5 * jnp.square(x), delta * (abs_x - 0.5 * delta))


def td_target(rewards, next_q, dones, gamma: float = 0.99):
    return rewards + gamma * (1.0 - dones.astype(jnp.float32)) * next_q
