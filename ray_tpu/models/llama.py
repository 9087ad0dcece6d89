"""Llama-family decoder in flax.linen, TPU-first.

Design points (vs the reference's torch models, e.g. rllib catalog /
serve LLM replicas):
- bf16 activations, param dtype configurable (f32 master weights by default;
  the optimizer state stays f32 — mixed-precision policy lives here, not in a
  wrapper class like torch AMP).
- Param-tree paths (`embed/embedding`, `layers_N/attn/wq/kernel`, ...) are the
  contract with `ray_tpu.parallel.sharding.llama_rules()` — renaming a module
  changes how it shards.
- Attention impl is selectable: "flash" (pallas), "xla" (einsum reference),
  "ring" (sequence-parallel, needs an `sp` mesh axis), or "auto".
- Decode path uses a static-shape `KVCache` so every step hits the same
  compiled program.
- `remat=True` checkpoints each block (jax.checkpoint) — the TPU equivalent
  of activation checkpointing, trading HBM for recompute.
"""

import contextlib
import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from ray_tpu.models.moe import MoEMLP
from ray_tpu.ops.attention import (apply_rope, blockwise_prefill_attention,
                                   decode_attention, mha_reference)
from ray_tpu.ops.flash_attention import _CONT_BLOCK_KV as CONT_BLOCK_KV
from ray_tpu.ops.flash_attention import (continuation_blocks, flash_attention,
                                         flash_continuation)
from ray_tpu.ops.linear_attention import causal_conv, kda_chunked, kda_step
from ray_tpu.ops.paged_attention import (PagedKVCache, paged_attention,
                                         paged_attention_reference,
                                         row_keys_values, row_pages,
                                         sparse_attention_reference,
                                         sparse_paged_decode,
                                         sparse_paged_prefill,
                                         write_layer_tokens)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ssd import ssd_chunked, ssd_step


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16       # activations
    param_dtype: Any = jnp.float32  # master weights
    attn_impl: str = "auto"         # auto | flash | xla | ring
    sp_axis: str = "sp"             # mesh axis for ring attention
    remat: bool = False
    # ---- mixture-of-experts (Mixtral-family; models/moe.py). 0 = dense.
    # When n_experts > 0 every `moe_every`-th block's FFN becomes a
    # top-k-routed expert bank; weights carry a leading [E, ...] dim that
    # `parallel.sharding.llama_rules()` shards over the `ep` mesh axis.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 1              # 1 = every block (Mixtral layout)
    capacity_factor: float = 1.25   # per-expert token budget multiplier
    router_aux_weight: float = 0.01  # load-balance loss weight (sowed)
    expert_dim: int = 0             # an expert's width; 0 = ffn_dim
    # ---- what the Qwen3-MoE trunk and a learned sparse attention add; all
    # off by default, so the other presets trace to the programs they did.
    qk_norm: bool = False           # RMS norm of q and k over each head
    # rotary positions of three components (temporal, height, width): how
    # many of the head_dim/2 frequencies each turns. Used when `positions`
    # is [3, B, T]; [B, T] positions (text) are one-component rotary.
    rope_sections: Optional[Tuple[int, ...]] = None
    # lightning indexer (DeepSeek-V3.2-Exp): `index_heads` query heads of
    # `index_dim` against one key head score every earlier token, and
    # attention sees the `index_topk` best (all while there are no more).
    # 0 = dense attention. Needs the paged cache's third pool to decode.
    index_heads: int = 0
    index_dim: int = 64
    index_topk: int = 0
    # ---- a hybrid of full and linear attention (Kimi Linear's layout, which
    # Solar-Open2 takes): layer i has full softmax attention iff
    # i % full_attn_every == 0, the others the gated delta rule
    # (ops/linear_attention.py) over a fixed state a head. 0 = every layer
    # full. Only the full layers have keys and values in the paged cache.
    full_attn_every: int = 0
    linear_heads: int = 0           # heads of the linear layers
    linear_key_dim: int = 128       # a head's key (and query) width
    linear_value_dim: int = 128
    linear_conv: int = 4            # width of the short convolution
    linear_rank: int = 128          # of the decay's and the gate's low-rank pair
    use_rope: bool = True           # False: no positions anywhere (NoPE)
    attn_gate: bool = False         # full layers: out * sigmoid(x Wgate)
    # ---- what the DeepSeek-V3 family's router and banks add to the MoE
    router_score: str = "softmax"   # softmax | sigmoid (gates renormalised)
    n_shared_experts: int = 0       # SwiGLU experts every token takes
    # a chip's SHARE of the routed experts: the router scores all n_experts,
    # the bank holds `experts_held` of them from `experts_first` on and
    # computes the pairs that fall on those (0 = the bank holds them all)
    experts_held: int = 0
    experts_first: int = 0
    # ---- sliding-window layers beside full ones (Cohere's Command family):
    # the kind of layer i is layer_types[i % len(layer_types)], "sliding"
    # (rotary positions; query t sees key s iff t - sliding_window < s <= t;
    # its keys and values live in the paged cache's WINDOW pool) or "full"
    # (no positions at all, causal, the full pool). None = every layer as
    # the fields above say.
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 0
    # rotary turns the pairs (2i, 2i + 1) of a head (GPT-J's layout), which
    # is the layout of such a model's weights as published and as given. The
    # weights it is SERVED on have the pairs split, a head's columns of wq
    # and wk reordered [0, 2, ..., 1, 3, ...], and run with this False:
    # `split_rotary_pairs`, once, where `LLMServer` takes its weights. The
    # uncached forward and training keep this form on the given weights.
    rope_interleaved: bool = False
    # wq's and wk's kernels on the layers that turn q and k are stored
    # [out, in], as the v5e's compiler reads them in every serving program
    # (given [in, out] it transposes the WEIGHT in every call). Set by
    # `split_rotary_pairs` on the tree it rebuilds, by nothing else.
    qk_out_major: bool = False
    # attention and FFN both read ONE norm of the block's input and are
    # summed into the residual
    parallel_block: bool = False
    norm: str = "rms"               # rms | layer (mean-centred, no bias)
    logit_scale: float = 1.0
    shared_average: bool = False    # the shared experts' MEAN, not their sum
    # ---- a Mamba-2 mixer BESIDE the attention heads in EVERY block
    # (Falcon-H1): both read one norm of the block's input, their outputs are
    # summed into the residual, and the FFN is a sub-block of its own. Every
    # layer then has keys and values in the paged cache AND a recurrent state
    # a slot (ops/ssd.py). 0 = no mixer.
    ssm_heads: int = 0
    ssm_head_dim: int = 128
    ssm_state: int = 256            # a channel's state
    ssm_groups: int = 1             # groups of heads that share B and C
    ssm_conv: int = 4               # width of the short convolution (biased)
    ssm_chunk: int = 128            # the chunked program's chunk
    # muP multipliers, fixed numbers of the published config: on the
    # embedding, on attention's input, keys and output, on the mixer's input,
    # the five segments of its projection (z, x, B, C, dt) and its output, on
    # the FFN's gate and output (`logit_scale` is the head's)
    embed_scale: float = 1.0
    attn_in_scale: float = 1.0
    key_scale: float = 1.0
    attn_out_scale: float = 1.0
    ssm_in_scale: float = 1.0
    ssm_scales: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_scale: float = 1.0
    mlp_gate_scale: float = 1.0
    mlp_down_scale: float = 1.0

    def layer_kind(self, layer_idx: int) -> Optional[str]:
        """"sliding" or "full" under a `layer_types` pattern, else None."""
        if not self.layer_types:
            return None
        return self.layer_types[layer_idx % len(self.layer_types)]

    @property
    def n_window_layers(self) -> int:
        return sum(self.layer_kind(i) == "sliding"
                   for i in range(self.n_layers))

    def is_linear(self, layer_idx: int) -> bool:
        return bool(self.full_attn_every) and layer_idx % self.full_attn_every != 0

    def turns_qk(self, layer_idx: int) -> bool:
        """Whether the layer turns q and k by rotary positions."""
        return (self.use_rope and not self.is_linear(layer_idx)
                and self.layer_kind(layer_idx) != "full")

    @property
    def n_linear_layers(self) -> int:
        return sum(self.is_linear(i) for i in range(self.n_layers))

    @property
    def n_state_layers(self) -> int:
        """Layers that hold a recurrent state a slot: the linear ones, or
        every layer of a model with a mixer beside its attention heads."""
        return self.n_layers if self.ssm_heads else self.n_linear_layers

    def state_schema(self) -> Optional[dict]:
        """What one slot's state is made of, as `PagedKVCache.init` takes it
        (`linear`, less the snapshot pool's size); None for a model without."""
        if self.ssm_heads:
            return dict(
                layers=self.n_layers, heads=self.ssm_heads,
                key_dim=self.ssm_state, value_dim=self.ssm_head_dim,
                conv=self.ssm_conv - 1,
                channels=(self.ssm_heads * self.ssm_head_dim
                          + 2 * self.ssm_groups * self.ssm_state))
        if not self.n_linear_layers:
            return None
        return dict(
            layers=self.n_linear_layers, heads=self.linear_heads,
            key_dim=self.linear_key_dim, value_dim=self.linear_value_dim,
            conv=self.linear_conv - 1,
            channels=self.linear_heads * (2 * self.linear_key_dim
                                          + self.linear_value_dim))

    def kv_layer(self, layer_idx: int) -> int:
        """Where a layer's keys and values lie in its pool (under a
        `layer_types` pattern: among the layers of its own kind)."""
        if self.layer_types:
            kind = self.layer_kind(layer_idx)
            return sum(self.layer_kind(i) == kind for i in range(layer_idx))
        return layer_idx // self.full_attn_every if self.full_attn_every else layer_idx

    def linear_index(self, layer_idx: int) -> int:
        """Which of the cache's states a layer with one owns."""
        if self.ssm_heads:
            return layer_idx
        return layer_idx - layer_idx // self.full_attn_every - 1

    # ---- presets (sizes follow the Llama family; test config is `tiny`).
    # kwargs override the preset's own values (e.g. tiny(max_seq_len=64)).
    @staticmethod
    def tiny(**kw):
        return LlamaConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=128,
            max_seq_len=128, rope_theta=10000.0), **kw})

    @staticmethod
    def moe_tiny(**kw):
        """Test-scale Mixtral layout: every FFN is a 4-expert top-2 bank."""
        return LlamaConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=128, max_seq_len=128,
            rope_theta=10000.0, n_experts=4, moe_top_k=2), **kw})

    @staticmethod
    def keye_tiny(**kw):
        """Test-scale Keye-VL-2.0 language model: q/k norm, three-component
        rotary, 16 experts top-2 of width 32 (the grouped product), an indexer
        of 2 heads of 8 that selects 16 keys."""
        return LlamaConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=128, max_seq_len=128,
            rope_theta=10000.0, norm_eps=1e-6, n_experts=16, moe_top_k=2,
            expert_dim=32, qk_norm=True, rope_sections=(2, 3, 3),
            index_heads=2, index_dim=8, index_topk=16), **kw})

    @staticmethod
    def keye_vl2_30b_a3b(**kw):
        """Keye-VL-2.0-30B-A3B, the language model (the vision tower is not
        built): Qwen3-MoE trunk, 128 experts of 768, 8 a token, GQA 32 / 4
        of 128, q/k norm, rotary sections [16, 24, 24], a 16-head indexer of
        width 64 selecting 2048 keys. `ffn_dim` is the config's unused
        `intermediate_size`: every layer has experts."""
        return LlamaConfig(**{**dict(
            vocab_size=151936, d_model=2048, n_layers=48, n_heads=32,
            n_kv_heads=4, head_dim=128, ffn_dim=6144, max_seq_len=262144,
            rope_theta=1e7, norm_eps=1e-6, n_experts=128, moe_top_k=8,
            expert_dim=768, qk_norm=True, rope_sections=(16, 24, 24),
            index_heads=16, index_dim=64, index_topk=2048), **kw})

    @staticmethod
    def solar_tiny(**kw):
        """Test-scale Solar-Open2: one full-attention layer in four (no
        rotary, an output gate) beside gated delta-rule layers, 16 experts
        of 32 scored by a sigmoid with 2 a token, one shared expert."""
        return LlamaConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=128, max_seq_len=128,
            n_experts=16, moe_top_k=2, expert_dim=32, full_attn_every=4,
            linear_heads=4, linear_key_dim=16, linear_value_dim=16,
            linear_rank=16, use_rope=False, attn_gate=True,
            router_score="sigmoid", n_shared_experts=1), **kw})

    @staticmethod
    def solar_open2_250b(**kw):
        """Solar-Open2-250B: 48 layers of which every fourth is GQA 64 / 8
        of 128 without positions and with an output gate, the others KDA
        with 64 heads of 128 x 128; 320 experts of 1280 scored by a sigmoid,
        8 a token, one shared. `ffn_dim` is the config's unused
        `intermediate_size`."""
        return LlamaConfig(**{**dict(
            vocab_size=196608, d_model=4096, n_layers=48, n_heads=64,
            n_kv_heads=8, head_dim=128, ffn_dim=10240, max_seq_len=262144,
            n_experts=320, moe_top_k=8, expert_dim=1280, full_attn_every=4,
            linear_heads=64, linear_key_dim=128, linear_value_dim=128,
            linear_rank=128, use_rope=False, attn_gate=True,
            router_score="sigmoid", n_shared_experts=1), **kw})

    @staticmethod
    def command_tiny(**kw):
        """Test-scale Command A+: three sliding layers (window 16,
        interleaved rotary) to one full layer without positions, two periods,
        a parallel block under a mean-centred norm, 4 query heads a kv head,
        16 experts of 32 scored by a sigmoid with 2 a token beside 2 shared
        experts that are averaged, a tied embedding."""
        return LlamaConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=8, n_heads=8,
            n_kv_heads=2, head_dim=16, ffn_dim=32, max_seq_len=128,
            rope_theta=50000.0, tie_embeddings=True, n_experts=16,
            moe_top_k=2, expert_dim=32, router_score="sigmoid",
            n_shared_experts=2, shared_average=True,
            layer_types=("sliding", "sliding", "sliding", "full"),
            sliding_window=16, rope_interleaved=True, parallel_block=True,
            norm="layer"), **kw})

    @staticmethod
    def command_a_plus(**kw):
        """Command A+ (command-a-plus-05-2026, `cohere2_moe`, 218B-A25B): 32
        layers, three sliding (window 4096, interleaved rotary, theta 50000)
        to one full without positions; 128 query heads of 128 on a hidden
        size of 4096 over 8 kv heads; a parallel block under a LayerNorm
        without bias; 128 experts of 4096 scored by a sigmoid, 8 a token,
        beside 4 shared experts that are averaged; a tied embedding."""
        return LlamaConfig(**{**dict(
            vocab_size=262144, d_model=4096, n_layers=32, n_heads=128,
            n_kv_heads=8, head_dim=128, ffn_dim=4096, max_seq_len=131072,
            rope_theta=50000.0, norm_eps=1e-5, tie_embeddings=True,
            n_experts=128, moe_top_k=8, expert_dim=4096,
            router_score="sigmoid", n_shared_experts=4, shared_average=True,
            layer_types=("sliding", "sliding", "sliding", "full"),
            sliding_window=4096, rope_interleaved=True, parallel_block=True,
            norm="layer"), **kw})

    @staticmethod
    def falcon_h1_tiny(**kw):
        """Test-scale Falcon-H1: in every block 5 query heads a kv head
        beside a Mamba-2 mixer of 8 heads of 16 in 2 groups with a state of
        32, chunks of 8, and the published multipliers."""
        return LlamaConfig(**{**_FALCON_H1_MULTIPLIERS, **dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=5,
            n_kv_heads=1, head_dim=16, ffn_dim=128, max_seq_len=128,
            rope_theta=1e11, ssm_heads=8, ssm_head_dim=16, ssm_state=32,
            ssm_groups=2, ssm_chunk=8), **kw})

    @staticmethod
    def falcon_h1_34b(**kw):
        """Falcon-H1-34B-Instruct (`falcon_h1`): 72 blocks alike, in each 20
        query heads of 128 over 4 kv heads (rotary, theta 1e11) beside a
        Mamba-2 mixer of 32 heads of 128 in 2 groups with a state of 256,
        both on one norm and summed; an FFN of 21504; eight muP multipliers;
        an untied head over 261,120 tokens."""
        return LlamaConfig(**{**_FALCON_H1_MULTIPLIERS, **dict(
            vocab_size=261120, d_model=5120, n_layers=72, n_heads=20,
            n_kv_heads=4, head_dim=128, ffn_dim=21504, max_seq_len=262144,
            rope_theta=1e11, norm_eps=1e-5, ssm_heads=32, ssm_head_dim=128,
            ssm_state=256, ssm_groups=2, ssm_conv=4, ssm_chunk=128), **kw})

    @staticmethod
    def mixtral_8x7b(**kw):
        """Mixtral-8x7B shape: Llama-7B trunk, 8 experts, top-2 routing."""
        return LlamaConfig(**{**dict(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, head_dim=128, ffn_dim=14336, max_seq_len=32768,
            rope_theta=1000000.0, n_experts=8, moe_top_k=2), **kw})

    @staticmethod
    def llama_125m(**kw):
        return LlamaConfig(**{**dict(
            vocab_size=32000, d_model=768, n_layers=12,
            n_heads=12, n_kv_heads=12, head_dim=64,
            ffn_dim=2048, max_seq_len=2048), **kw})

    @staticmethod
    def llama_1b(**kw):
        return LlamaConfig(**{**dict(
            vocab_size=32000, d_model=2048, n_layers=16,
            n_heads=32, n_kv_heads=8, head_dim=64,
            ffn_dim=5632, max_seq_len=4096), **kw})

    @staticmethod
    def llama_8b(**kw):
        return LlamaConfig(**kw)  # defaults above are 8B

    @staticmethod
    def llama_70b(**kw):
        return LlamaConfig(**{**dict(
            d_model=8192, n_layers=80, n_heads=64,
            n_kv_heads=8, head_dim=128, ffn_dim=28672), **kw})


# Falcon-H1-34B-Instruct's config.json: embedding_multiplier,
# lm_head_multiplier, attention_in / key / attention_out, ssm_in,
# ssm_multipliers (z, x, B, C, dt), ssm_out, mlp_multipliers (gate, down)
_FALCON_H1_MULTIPLIERS = dict(
    embed_scale=5.656854249492381, logit_scale=0.0078125,
    attn_in_scale=1.0, key_scale=0.011048543456039804,
    attn_out_scale=0.0375, ssm_in_scale=0.25,
    ssm_scales=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                0.3535533905932738),
    ssm_out_scale=0.08838834764831845,
    mlp_gate_scale=0.1767766952966369, mlp_down_scale=0.011160714285714284)


class KVCache(flax.struct.PyTreeNode):
    """Static-shape per-layer K/V cache: lists of [B, Smax, Kh, D] arrays.

    `length` counts valid tokens per batch row (same for all rows in the
    simple decode loop; per-row for continuous batching in serve/llm).

    Capacity invariant (caller-enforced, host-side): length + new_tokens must
    stay <= Smax. XLA's dynamic_update_slice clamps out-of-range starts, so an
    overflowing write would silently overwrite the cache tail instead of
    erroring — drivers (serve/llm, generate loops) must stop or evict at
    capacity; a data-dependent raise can't live inside jit.

    Scan contract: the decode-step program (t == 1) is also the body of
    serve/llm's fused multi-token chunk — the cache is CARRIED through a
    lax.scan, so the step must stay shape-stable with no host callbacks,
    and the capacity invariant applies per scan step (the serve tick loop
    clamps its chunk length to the row with the most remaining room). A
    row whose length is frozen mid-scan (terminated slot) keeps taking one
    masked write per step at that frozen position — garbage past `length`
    is never readable (absolute-position mask) and is overwritten when the
    row is reused."""
    k: Tuple[jax.Array, ...]
    v: Tuple[jax.Array, ...]
    length: jax.Array  # [B] int32

    @staticmethod
    def init(cfg: LlamaConfig, batch: int, max_len: Optional[int] = None,
             dtype=None):
        max_len = max_len or cfg.max_seq_len
        dtype = dtype or cfg.dtype
        shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        zeros = lambda: jnp.zeros(shape, dtype)
        return KVCache(
            k=tuple(zeros() for _ in range(cfg.n_layers)),
            v=tuple(zeros() for _ in range(cfg.n_layers)),
            length=jnp.zeros((batch,), jnp.int32))


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        normed = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """Mean-centred norm with a scale and no bias, in f32."""
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        xf = xf - jnp.mean(xf, -1, keepdims=True)
        normed = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def _norm(cfg: "LlamaConfig", name: str):
    return (LayerNorm if cfg.norm == "layer" else RMSNorm)(
        cfg.norm_eps, cfg.dtype, name=name)


class Indexer(nn.Module):
    """The lightning indexer's projections: `index_heads` query heads, one
    key head (LayerNorm, cached in the third pool) and a weight a head, all
    from the block's normed input; rotary by the temporal position."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        b, t, _ = x.shape
        qi = dense(cfg.index_heads * cfg.index_dim, name="wq")(x)
        ki = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="k_norm")(
            dense(cfg.index_dim, name="wk")(x))
        wi = dense(cfg.index_heads, name="w")(x)
        qi = apply_rope(qi.reshape(b, t, cfg.index_heads, cfg.index_dim),
                        positions, cfg.rope_theta)
        ki = apply_rope(ki[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        return qi, ki, wi


def _band(t: int, window: int):
    """[1, T, T] True where query t sees key s through a causal window."""
    at = jnp.arange(t)
    return ((at[None, :] <= at[:, None])
            & (at[None, :] > at[:, None] - window))[None]


def _chunk_local_attention(cfg: LlamaConfig, q, k, v, window=None):
    """Causal attention of a chunk over itself (a fresh row's first chunk
    into the paged cache); honors attn_impl like the cache=None branch. A
    sliding layer's chunk longer than its window (test sizes: the real
    chunk is a quarter of the window) takes the masked XLA form."""
    if window is not None and q.shape[1] > window:
        return mha_reference(q, k, v, causal=False,
                             mask=_band(q.shape[1], window))
    impl = cfg.attn_impl
    if impl in ("auto", "ring"):
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    return (flash_attention(q, k, v, causal=True) if impl == "flash"
            else mha_reference(q, k, v, causal=True))


# f32 scores of a chunk against its row's whole capacity that XLA may hold at
# once (`decode_attention`); past it the row goes by key blocks. Mixtral's
# chunk of 512 x 32 heads over 2304 keys is 151 MB; Solar's of 1024 x 64
# over 40,960 would be 10.7 GB.
_SCORES_AT_ONCE_BYTES = 2 ** 30


def _continuation_attention(q, cache: PagedKVCache, layer_idx, positions,
                            window=None):
    """Attention of a prefill chunk (B is 1: the row view) whose tokens sit
    at `positions` [B, T] over the row's pages, its own keys written: the row's
    pages copied out contiguous (slot s = absolute position s; the padded
    table's placeholder pages sit past every valid query position and are
    masked) under `decode_attention`'s absolute-position causal mask.

    On the TPU one flash kernel over the pages head-major as the pool holds
    them, as far as the chunk's last query reaches; a chunk its blocks do not
    tile (a bucket clamped to what the row has left) and every chunk off the
    TPU take the XLA forms: all keys at once where the scores fit, by key
    blocks where they would be gigabytes.

    `window` (a sliding layer; `cache` is then the window view): only the
    pages from the key block of the first visible key on are copied out, and
    every form masks what lies before a query's window."""
    _, t, h, _ = q.shape
    g = h // cache.k_pages.shape[1]
    on_tpu = (jax.default_backend() == "tpu"
              and continuation_blocks(t, g, q.dtype) is not None)
    # (the start is taken after the pages, where it always was: the other
    # configurations' lowered programs are held to their text)
    if on_tpu and window is None:
        return flash_continuation(q, *row_pages(cache, layer_idx),
                                  positions[:, 0])
    if on_tpu:
        ps = cache.page_size
        per_block = max(1, CONT_BLOCK_KV // ps)     # pages a key block
        start = positions[:, 0]
        first = (jnp.maximum(start - window + 1, 0) // (per_block * ps)
                 * per_block)
        n_pages = (window + t - 2) // ps + 2 + per_block
        return flash_continuation(
            q, *row_pages(cache, layer_idx, first=first, n_pages=n_pages),
            start, window=window)
    k_all, v_all = row_keys_values(cache, layer_idx)
    at_once = 4 * t * h * k_all.shape[1] <= _SCORES_AT_ONCE_BYTES
    return (decode_attention if at_once else blockwise_prefill_attention)(
        q, k_all, v_all, positions[:, 0],
        **({} if window is None else {"window": window}))


class _OutMajorDense(nn.Module):
    """`nn.Dense` without bias on a kernel stored [out, in]."""
    features: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (self.features, x.shape[-1]), self.param_dtype)
        return jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (1,)), ((), ())))


class Attention(nn.Module):
    cfg: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, positions, cache: Optional[KVCache],
                 paged_chunk_local: bool = False):
        cfg = self.cfg
        layer_idx = self.layer_idx
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        b, t, _ = x.shape
        kind = cfg.layer_kind(layer_idx)
        turned = cfg.turns_qk(layer_idx)
        qk = (partial(_OutMajorDense, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype)
              if turned and cfg.qk_out_major else dense)
        q = qk(cfg.n_heads * cfg.head_dim, name="wq")(x)
        k = qk(cfg.n_kv_heads * cfg.head_dim, name="wk")(x)
        v = dense(cfg.n_kv_heads * cfg.head_dim, name="wv")(x)
        if cfg.key_scale != 1.0:
            k = k * cfg.key_scale
        q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(k)
        # a sliding layer's mask, and the argument that carries it
        window = cfg.sliding_window if kind == "sliding" else None
        win = {} if window is None else {"window": window}
        if turned:
            turn = ({"interleaved": True} if cfg.rope_interleaved else {})
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_sections,
                           **turn)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_sections,
                           **turn)
        if cfg.full_attn_every or kind:   # a pool holds its kind's layers
            layer_idx = cfg.kv_layer(layer_idx)
        scope = (jax.named_scope("attn_window" if window else "attn_full")
                 if kind else contextlib.nullcontext())
        if positions.ndim == 3:
            # three-component rotary; the order in the sequence (where the
            # cache is written, what is causal) is the row's own count
            temporal = positions[0]
            positions = (jnp.broadcast_to(jnp.arange(t)[None], (b, t))
                         if cache is None else
                         cache.length[:, None] + jnp.arange(t)[None])
        else:
            temporal = positions

        new_cache_kv = None
        if cfg.index_topk:
            with jax.named_scope("indexer"):
                qi, ki, wi = Indexer(cfg, name="indexer")(x, temporal)
            if isinstance(cache, PagedKVCache):
                cache = write_layer_tokens(cache, layer_idx, k, v, positions,
                                           idx_new=ki)
                if t == 1:
                    out = sparse_paged_decode(
                        q[:, 0], qi[:, 0], wi[:, 0], cache, layer_idx,
                        positions[:, -1] + 1, cfg.index_topk)[:, None]
                elif paged_chunk_local and t <= cfg.index_topk:
                    # a fresh row's first chunk of no more than `index_topk`
                    # tokens: every query selects all its keys, so
                    # chunk-local causal attention is exact
                    out = _chunk_local_attention(cfg, q, k, v)
                else:
                    out = sparse_paged_prefill(q, qi, wi, cache, layer_idx,
                                               positions, cfg.index_topk)
                new_cache_kv = cache
            elif cache is not None:
                raise NotImplementedError(
                    "learned sparse attention decodes through the paged "
                    "cache (its indexer keys live in the third pool)")
            else:
                out = sparse_attention_reference(q, k, v, qi, ki, wi,
                                                 cfg.index_topk)
        elif isinstance(cache, PagedKVCache):
            # Paged decode/prefill (vLLM memory model, ops/paged_attention):
            # write this layer's K/V into its page slice, then attend. The
            # cache threads through the block stack; every write and read
            # addresses the stacked pool by (layer, page), in place on the
            # donated pool (write_layer_tokens: a layer taken out of it, or a
            # scatter into it, moved a pool-sized array). A sliding layer
            # does all of it on the window pool and its table.
            whole = cache
            if window is not None:
                cache = cache.window_view()
            with scope:
                cache = write_layer_tokens(cache, layer_idx, k, v, positions)
                if t == 1:
                    # decode: pallas kernel walks the block table of the
                    # stacked pool, whole as the cache holds it (XLA gather
                    # reference off-TPU, same arguments, same numerics)
                    impl = (paged_attention if jax.default_backend() == "tpu"
                            else paged_attention_reference)
                    out = impl(q[:, 0], cache.k_pages, cache.v_pages,
                               layer_idx, cache.block_tables,
                               positions[:, -1] + 1, **win)[:, None]
                elif paged_chunk_local:
                    # FIRST chunk of a fresh row (start==0, no cached prefix —
                    # the caller asserts this statically): chunk-local causal
                    # attention is exact, no page gather. The hot cold-prompt
                    # TTFT path; honors attn_impl like the cache=None branch.
                    out = _chunk_local_attention(cfg, q, k, v, **win)
                else:
                    # chunked prefill continuation: queries must see the row's
                    # CACHED prefix (chunks 2+ of a long prompt, and
                    # prefix-cache hits start mid-prompt), not just their own
                    # chunk — chunk-local causal attention here was the r4 bug
                    # that made multi-chunk paged prefill numerically wrong.
                    out = _continuation_attention(q, cache, layer_idx,
                                                  positions, **win)
            if window is not None:
                cache = whole.merge_window(cache)
            new_cache_kv = cache
        elif cache is not None:
            # Decode: write current K/V at `length`, attend over the cache.
            k_cache = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0)
            )(cache.k[layer_idx], k, cache.length)
            v_cache = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0)
            )(cache.v[layer_idx], v, cache.length)
            out = decode_attention(q, k_cache, v_cache, cache.length, **win)
            new_cache_kv = (k_cache, v_cache)
        else:
            impl = cfg.attn_impl
            if impl == "auto":
                impl = "flash" if jax.default_backend() == "tpu" else "xla"
            if window is not None:
                # no flash kernel takes a window mask through its backward
                # pass: the uncached forward of a sliding layer is XLA's
                out = mha_reference(q, k, v, causal=False,
                                    mask=_band(t, window))
            elif impl == "flash":
                out = flash_attention(q, k, v, causal=True)
            elif impl == "ring":
                out = ring_attention(q, k, v, axis_name=cfg.sp_axis, causal=True)
            else:
                out = mha_reference(q, k, v, causal=True)

        out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
        if cfg.attn_gate:
            out = out * nn.sigmoid(
                dense(cfg.n_heads * cfg.head_dim, name="w_gate")(x))
        return dense(cfg.d_model, name="wo")(out), new_cache_kv


def _decay_bias_init(lo: float, hi: float):
    """`dt_bias` such that softplus(dt_bias), a channel's decay rate with
    `A_log` 0, is log-uniform in [lo, hi]: alpha = exp(-rate) then spans
    (exp(-hi), exp(-lo))."""
    def init(key, shape, dtype=jnp.float32):
        rate = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(lo), jnp.log(hi)))
        return jnp.log(jnp.expm1(rate)).astype(dtype)
    return init


class LinearAttention(nn.Module):
    """A gated delta-rule layer (KDA): q, k, v through a short causal
    convolution and a silu, q and k normalised a head; a per-channel decay
    from a low-rank pair; a write strength in (0, 2); the recurrence of
    `ops/linear_attention.py`; a per-head RMS norm and a low-rank sigmoid
    gate before the output projection. With a paged cache the layer's state
    and the convolution's last inputs are the cache's, a slot each; without
    one (training, the uncached forward) the sequence starts from zeros."""
    cfg: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, cache, n_valid=None, fresh: bool = False):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        b, t, _ = x.shape
        h, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
        f32 = jnp.float32
        qkv = jnp.concatenate([dense(h * dk, name="wq")(x),
                               dense(h * dk, name="wk")(x),
                               dense(h * dv, name="wv")(x)], axis=-1)
        conv_w = self.param("conv", nn.initializers.normal(0.02),
                            (cfg.linear_conv, qkv.shape[-1]), cfg.param_dtype)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
        dt_bias = self.param("dt_bias", _decay_bias_init(1e-4, 0.105),
                             (h * dk,), f32)
        rate = dense(h * dk, name="wf_b")(dense(cfg.linear_rank, name="wf_a")(x))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            rate.astype(f32) + dt_bias).reshape(b, t, h, dk)
        beta = 2.0 * nn.sigmoid(dense(h, name="wb")(x).astype(f32))

        li = cfg.linear_index(self.layer_idx)
        paged = isinstance(cache, PagedKVCache)
        if cache is not None and not paged:
            raise NotImplementedError(
                "a linear-attention layer decodes through the paged cache "
                "(its state is the cache's, a slot each)")
        if paged and not fresh:
            state, carried = cache.state[li], cache.conv[li]
        else:
            state = jnp.zeros((b, h, dk, dv), f32)
            carried = jnp.zeros((b, cfg.linear_conv - 1, qkv.shape[-1]),
                                cfg.dtype)
        qkv, carried = causal_conv(qkv, carried, conv_w, n_valid)
        qkv = nn.silu(qkv)
        q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
        q = unit(q.reshape(b, t, h, dk).astype(f32)) * dk ** -0.5
        k = unit(k.reshape(b, t, h, dk).astype(f32))
        v = v.reshape(b, t, h, dv)
        if t == 1 and paged:
            valid = None if n_valid is None else n_valid > 0
            o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], state, valid)
            o = o[:, None]
        else:
            o, state = kda_chunked(q, k, v, g, beta, state, n_valid)
        if paged:
            put = lambda old, new: old[:li] + (new,) + old[li + 1:]
            cache = cache.replace(state=put(cache.state, state),
                                  conv=put(cache.conv, carried))
        o = RMSNorm(cfg.norm_eps, cfg.dtype, name="o_norm")(o)
        gate = dense(h * dv, name="wg_b")(dense(cfg.linear_rank, name="wg_a")(x))
        o = o.reshape(b, t, h * dv) * nn.sigmoid(gate)
        return dense(cfg.d_model, name="wo")(o), (cache if paged else None)


class Mamba2Mixer(nn.Module):
    """A Mamba-2 mixer (SSD): one projection into a gate z, the inputs x and
    the group's B and C, and a step dt a head, each segment under its own
    multiplier; x, B and C through one short causal convolution with a bias
    and a silu; the recurrence of `ops/ssd.py` with a scalar decay a head;
    y * silu(z) under an RMS norm a group with a learned scale; the output
    projection. With a paged cache the layer's state and the convolution's
    last inputs are the cache's, a slot each; without one the sequence starts
    from zeros."""
    cfg: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, cache, n_valid=None, fresh: bool = False):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        b, t, _ = x.shape
        h, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_groups)
        f32 = jnp.float32
        inner, bc = h * p, g * n
        segments = (inner, inner, bc, bc, h)                 # z, x, B, C, dt
        mup = jnp.concatenate([jnp.full((w,), m, cfg.dtype)
                               for w, m in zip(segments, cfg.ssm_scales)])
        zxbcdt = dense(sum(segments), name="in_proj")(
            x * cfg.ssm_in_scale) * mup
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)
        conv_w = self.param("conv", nn.initializers.normal(0.02),
                            (cfg.ssm_conv, inner + 2 * bc), cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.normal(0.02),
                            (inner + 2 * bc,), cfg.param_dtype)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, f32, 1.0, 16.0)), (h,))
        skip = self.param("D", nn.initializers.ones, (h,), f32)
        # softplus(dt_bias), a head's step where the projection gives 0, is
        # log-uniform in [0.001, 0.1] (Mamba-2's own)
        dt_bias = self.param("dt_bias", _decay_bias_init(1e-3, 0.1), (h,), f32)

        li = cfg.linear_index(self.layer_idx)
        paged = isinstance(cache, PagedKVCache)
        if cache is not None and not paged:
            raise NotImplementedError(
                "a Mamba-2 mixer decodes through the paged cache (its state "
                "is the cache's, a slot each)")
        if paged and not fresh:
            state, carried = cache.state[li], cache.conv[li]
        else:
            state = jnp.zeros((b, h, n, p), f32)
            carried = jnp.zeros((b, cfg.ssm_conv - 1, inner + 2 * bc),
                                cfg.dtype)
        xbc, carried = causal_conv(xbc, carried, conv_w, n_valid,
                                   bias=conv_b, scope="ssd_conv")
        xbc = nn.silu(xbc)
        xs, bm, cm = jnp.split(xbc, [inner, inner + bc], axis=-1)
        xs = xs.reshape(b, t, h, p)
        bm, cm = bm.reshape(b, t, g, n), cm.reshape(b, t, g, n)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        a = -jnp.exp(a_log)
        if t == 1 and paged:
            valid = None if n_valid is None else n_valid > 0
            y, state = ssd_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                skip, state, valid)
            y = y[:, None]
        else:
            y, state = ssd_chunked(xs, dt, a, bm, cm, skip, state, n_valid,
                                   chunk=cfg.ssm_chunk)
        if paged:
            put = lambda old, new: old[:li] + (new,) + old[li + 1:]
            cache = cache.replace(state=put(cache.state, state),
                                  conv=put(cache.conv, carried))
        # the gate first, then the norm a group (`mamba_norm_before_gate`
        # false), in f32
        scale = self.param("norm", nn.initializers.ones, (inner,), f32)
        y = (y.reshape(b, t, inner) * nn.silu(z.astype(f32))).reshape(
            b, t, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + cfg.norm_eps)
        y = (y.reshape(b, t, inner) * scale).astype(cfg.dtype)
        return dense(cfg.d_model, name="out_proj")(y), (cache if paged
                                                        else None)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02))
        gate = dense(cfg.ffn_dim, name="w_gate")(x)
        up = dense(cfg.ffn_dim, name="w_up")(x)
        if cfg.mlp_gate_scale != 1.0:
            gate = gate * cfg.mlp_gate_scale
        out = dense(cfg.d_model, name="w_down")(nn.silu(gate) * up)
        return out if cfg.mlp_down_scale == 1.0 else out * cfg.mlp_down_scale


class Block(nn.Module):
    cfg: LlamaConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, positions, cache, paged_chunk_local=False,
                 n_valid=None):
        cfg = self.cfg
        if cfg.parallel_block:
            # attention and FFN both read ONE norm of the input and are
            # summed into the residual
            normed = _norm(cfg, "attn_norm")(x)
            h, new_kv = Attention(cfg, self.layer_idx, name="attn")(
                normed, positions, cache, paged_chunk_local)
            if cfg.n_experts > 0 and self.layer_idx % cfg.moe_every == 0:
                real = (None if n_valid is None else
                        jnp.arange(x.shape[1])[None] < n_valid[:, None])
                return x + h + MoEMLP(cfg, name="moe")(normed, real), new_kv
            return x + h + MLP(cfg, name="mlp")(normed), new_kv
        if cfg.ssm_heads:
            # the attention heads and the mixer read ONE norm and are summed;
            # the FFN is a sub-block of its own. The paged cache goes through
            # attention (keys and values) and then the mixer (the state).
            normed = RMSNorm(cfg.norm_eps, cfg.dtype, name="attn_norm")(x)
            h, new_kv = Attention(cfg, self.layer_idx, name="attn")(
                normed if cfg.attn_in_scale == 1.0
                else normed * cfg.attn_in_scale,
                positions, cache, paged_chunk_local)
            paged = isinstance(cache, PagedKVCache)
            m, new_cache = Mamba2Mixer(cfg, self.layer_idx, name="mamba")(
                normed, new_kv if paged else cache, n_valid,
                paged_chunk_local)
            x = x + m * cfg.ssm_out_scale + h * cfg.attn_out_scale
            normed = RMSNorm(cfg.norm_eps, cfg.dtype, name="mlp_norm")(x)
            return x + MLP(cfg, name="mlp")(normed), (new_cache if paged
                                                      else new_kv)
        normed = RMSNorm(cfg.norm_eps, cfg.dtype, name="attn_norm")(x)
        if cfg.is_linear(self.layer_idx):
            h, new_kv = LinearAttention(cfg, self.layer_idx, name="kda")(
                normed, cache, n_valid, paged_chunk_local)
        else:
            h, new_kv = Attention(cfg, self.layer_idx, name="attn")(
                normed, positions, cache, paged_chunk_local)
        x = x + h
        normed = RMSNorm(cfg.norm_eps, cfg.dtype, name="mlp_norm")(x)
        if cfg.n_experts > 0 and self.layer_idx % cfg.moe_every == 0:
            # which rows are real tokens, for the bank's own counts
            real = (None if n_valid is None else
                    jnp.arange(x.shape[1])[None] < n_valid[:, None])
            return x + MoEMLP(cfg, name="moe")(normed, real), new_kv
        return x + MLP(cfg, name="mlp")(normed), new_kv


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, cache: Optional[KVCache] = None,
                 return_hidden: bool = False, paged_chunk_local: bool = False,
                 n_valid=None, logits_at=None):
        """tokens [B, T] int32 → logits [B, T, V] (f32), new cache (or None).

        Prefill/train: cache=None, full causal attention. Decode: pass a
        KVCache; T is the number of new tokens (usually 1).

        `paged_chunk_local=True` (static; paged prefill only): the chunk is
        the FIRST tokens of a fresh row (start==0, no cached prefix), so
        chunk-local causal attention is exact and skips the full-row page
        gather — the hot cold-prompt path.

        `n_valid` [B] (a model with linear-attention layers): how many of
        the T tokens of each row are real. The rest (a prefill bucket's
        padding, a slot that does not decode this step) leave the row's
        recurrent state where it was.

        `logits_at` [B]: the one position of each row whose logits are
        wanted (a prefill chunk keeps its last real token's): the final norm
        and the head run on that row alone and the logits are [B, 1, V].

        `return_hidden=True` returns the final-norm hidden states [B, T, D]
        instead of logits — callers fuse the lm_head into a chunked loss
        (ops.losses.chunked_cross_entropy) to avoid materializing [B, T, V]."""
        cfg = self.cfg
        b, t = tokens.shape
        if positions is None:
            if cache is not None:
                positions = cache.length[:, None] + jnp.arange(t)[None, :]
            else:
                positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        x = embed(tokens)
        if cfg.embed_scale != 1.0:
            x = x * cfg.embed_scale

        block_cls = Block
        if cfg.remat and cache is None:
            block_cls = nn.remat(
                Block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        paged = isinstance(cache, PagedKVCache)
        new_k, new_v = [], []
        for i in range(cfg.n_layers):
            x, new_kv = block_cls(cfg, i, name=f"layers_{i}")(
                x, positions, cache, paged_chunk_local, n_valid)
            if paged:
                cache = new_kv  # thread the updated page pools layer→layer
            elif new_kv is not None:
                new_k.append(new_kv[0])
                new_v.append(new_kv[1])

        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = _norm(cfg, "final_norm")(x)
        if return_hidden:
            new_cache = None
            if paged:
                new_cache = cache.replace(lengths=cache.lengths + t)
            elif cache is not None:
                new_cache = KVCache(k=tuple(new_k), v=tuple(new_v),
                                    length=cache.length + t)
            return x, new_cache
        if cfg.tie_embeddings:
            logits = embed.attend(x)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype,
                              kernel_init=nn.initializers.normal(0.02),
                              name="lm_head")(x)
        logits = logits.astype(jnp.float32)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale

        new_cache = None
        if paged:
            new_cache = cache.replace(lengths=cache.lengths + t)
        elif cache is not None:
            new_cache = KVCache(k=tuple(new_k), v=tuple(new_v),
                                length=cache.length + t)
        return logits, new_cache


def _n_moe_layers(cfg: LlamaConfig) -> int:
    if cfg.n_experts <= 0:
        return 0
    return len(range(0, cfg.n_layers, cfg.moe_every))


@partial(jax.jit, static_argnums=(1,))
def _pairs_split(x, head_dim: int):
    """`x` `[..., heads * head_dim]` with every head's entries reordered
    [0, 2, 4, ..., 1, 3, 5, ...]: a rotary pair (2i, 2i + 1) becomes the
    pair (i, i + head_dim / 2). A kernel `[in, out]` comes back `[out, in]`
    (`LlamaConfig.qk_out_major`)."""
    pairs = x.reshape(x.shape[:-1] + (-1, head_dim // 2, 2))
    return pairs.swapaxes(-1, -2).reshape(x.shape).T


def split_rotary_pairs(params, cfg: LlamaConfig):
    """`(params', cfg')` that compute what `(params, cfg)` compute wherever
    q meets k of the same tree (the cached forward: a served model), with the
    rotate-half rotary in place of the interleaved one.

    Turning the pairs (2i, 2i + 1) of a head is turning the pairs
    (i, i + D/2) of the head with its columns reordered [0, 2, ..., 1, 3,
    ...], and q . k does not change when q and k carry the same reordering.
    So on every layer that turns q and k (`LlamaConfig.turns_qk`) the output
    columns of `wq` and `wk` (and a
    q/k norm's scale) are reordered inside each head, ONCE, and `cfg'` says
    `rope_interleaved=False`: the interleaved form's pair reshape, which the
    compiler folds through the projection into a relayout of the WEIGHT in
    every call, is gone from the program. The two kernels are rebuilt
    anyway, so they are stored `[out, in]` (`qk_out_major`), which is how
    every serving program reads them: handed `[in, out]` it transposes the
    weight in every call as well. Values, the output projection and every
    kernel see what they saw. Every other leaf of `params'` IS the leaf of
    `params`; a model whose rotary is not interleaved gets both arguments
    back as they are. Keys cached by one pair are not keys of the other."""
    if not (cfg.use_rope and cfg.rope_interleaved):
        return params, cfg
    turned = {f"layers_{i}" for i in range(cfg.n_layers) if cfg.turns_qk(i)}
    split = {("wq", "kernel"), ("wk", "kernel"),
             ("q_norm", "scale"), ("k_norm", "scale")}

    def leaf(path, x):
        keys = tuple(getattr(k, "key", None) for k in path)
        if (len(keys) >= 4 and keys[-4] in turned and keys[-3] == "attn"
                and keys[-2:] in split):
            return _pairs_split(x, cfg.head_dim)
        return x

    return (jax.tree_util.tree_map_with_path(leaf, params),
            dataclasses.replace(cfg, rope_interleaved=False,
                                qk_out_major=True))


def _attn_params(cfg: LlamaConfig) -> int:
    """Per-layer attention weights — single source for count AND flops so
    a layout change (biases, MLA, ...) can't desynchronize reported MFU
    from the real parameter count."""
    n = cfg.d_model * cfg.head_dim * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
    if cfg.attn_gate:
        n += cfg.d_model * cfg.head_dim * cfg.n_heads
    if cfg.qk_norm:
        n += 2 * cfg.head_dim
    if cfg.index_topk:   # wq, wk, w, and the key's LayerNorm
        n += cfg.d_model * (cfg.index_heads * cfg.index_dim + cfg.index_dim
                            + cfg.index_heads) + 2 * cfg.index_dim
    return n


def _linear_attn_params(cfg: LlamaConfig) -> int:
    """One gated delta-rule layer: q, k, v, o, the two low-rank pairs, beta,
    the convolution, A_log, dt_bias and the per-head norm."""
    h, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    wide = h * (2 * dk + dv)
    return (cfg.d_model * (wide + h * dv + h)
            + cfg.linear_rank * (2 * cfg.d_model + h * dk + h * dv)
            + cfg.linear_conv * wide + h + h * dk + dv)


def _ssm_params(cfg: LlamaConfig) -> int:
    """One Mamba-2 mixer: the two projections, the convolution with its bias,
    A_log, D, dt_bias and the gated norm's scale."""
    if not cfg.ssm_heads:
        return 0
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = cfg.ssm_groups * cfg.ssm_state
    return (cfg.d_model * (2 * inner + 2 * bc + cfg.ssm_heads)
            + inner * cfg.d_model + (cfg.ssm_conv + 1) * (inner + 2 * bc)
            + 3 * cfg.ssm_heads + inner)


def _mlp_params(cfg: LlamaConfig) -> int:
    """One dense SwiGLU FFN."""
    return 3 * cfg.d_model * cfg.ffn_dim


def _expert_params(cfg: LlamaConfig) -> int:
    """One expert of an MoE bank (`expert_dim` wide, else as the dense FFN)."""
    return 3 * cfg.d_model * (cfg.expert_dim or cfg.ffn_dim)


def llama_param_count(cfg: LlamaConfig) -> int:
    """Parameters the model HOLDS: a bank with a share of the experts
    (`experts_held`) counts those, its router all it scores."""
    norms = 1 if cfg.parallel_block else 2
    per_layer = (_attn_params(cfg) + _ssm_params(cfg) + _mlp_params(cfg)
                 + norms * cfg.d_model)
    total = cfg.n_layers * per_layer
    total += cfg.n_linear_layers * (_linear_attn_params(cfg)
                                    - _attn_params(cfg))
    # MoE blocks swap the dense FFN for E experts + a router
    n_moe = _n_moe_layers(cfg)
    held = (cfg.experts_held or cfg.n_experts) + cfg.n_shared_experts
    total += n_moe * (held * _expert_params(cfg) - _mlp_params(cfg)
                      + cfg.d_model * cfg.n_experts)
    embed = cfg.vocab_size * cfg.d_model
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    return total + embed + head + cfg.d_model


def llama_compute_flops(cfg: LlamaConfig, batch: int, seq: int) -> float:
    """Training FLOPs per step ≈ 6·N_active·tokens + attention term
    (causal). For MoE, N_active counts top_k experts per token, not the
    full bank — the honest denominator for MFU."""
    n_moe = _n_moe_layers(cfg)
    n_dense = cfg.n_layers - n_moe
    n_active = (cfg.n_layers * (_attn_params(cfg) + _ssm_params(cfg))
                + cfg.n_linear_layers * (_linear_attn_params(cfg)
                                         - _attn_params(cfg))
                + n_dense * _mlp_params(cfg)
                + n_moe * ((cfg.moe_top_k + cfg.n_shared_experts)
                           * _expert_params(cfg)
                           + cfg.d_model * cfg.n_experts))
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    n_active += head
    tokens = batch * seq
    full = cfg.n_layers - cfg.n_linear_layers
    attn = 6 * full * cfg.n_heads * cfg.head_dim * batch * seq * seq  # fwd 2 matmuls + bwd, halved for causal
    return 6.0 * n_active * tokens + attn
