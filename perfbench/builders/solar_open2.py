"""Builder for `solar-open2-250b-serve`: Solar-Open2-250B as one chip's share
through `ray_tpu.models.llama` (`LlamaConfig.solar_open2_250b`: one
full-attention layer in four without positions and with an output gate, the
others gated delta-rule layers whose state the paged cache holds a slot; a
sigmoid router over all 320 experts with 8 a token, of which this chip's bank
holds 40, beside a shared expert; a vocabulary slice). Everything here runs
INSIDE the actor that holds the chip; the parent process never imports this
file's jax. The same functions as `keye_vl2.py`.
"""

import os

from perfbench.builders.llama_family import seed_key

_PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "ops", "linear_attention.py")

REHEARSAL = dict(vocab=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn=128, n_experts=16, top_k=2, expert_dim=32,
                 norm_eps=1e-5, full_attn_every=4, linear_heads=4,
                 linear_key_dim=16, linear_value_dim=16, linear_conv=4,
                 linear_rank=16, experts_held=4, experts_first=0, n_shared=1)


def model_sizes(config: dict, rehearse: bool = False) -> dict:
    """The configuration's sizes under the benchmark's own names, from the
    keys of its file (or the `solar_tiny` stand-ins for a rehearsal).
    `n_experts` is what the router scores, `experts_held` what the chip's
    bank holds; `vocab` is the slice; `ffn` is the config's
    `intermediate_size`, which no layer uses."""
    if not os.path.exists(_PROGRAM):
        # a checkout from before the program had such layers: say so at once,
        # in the parent process, before any worker or chip is taken
        raise SystemExit(
            f"this checkout's ray_tpu cannot run {config.get('name')!r}: it "
            f"has no linear-attention layers ({_PROGRAM} is not there)")
    if rehearse:
        return dict(REHEARSAL)
    lin = config["linear_attn_config"]
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn=config["intermediate_size"],
        n_experts=config["n_routed_experts_scored"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        norm_eps=float(config["rms_norm_eps"]),
        full_attn_every=config["gqa_interval"] + 1,
        linear_heads=lin["num_heads"], linear_key_dim=lin["head_dim"],
        linear_value_dim=lin["head_dim"],
        linear_conv=lin["short_conv_kernel_size"],
        linear_rank=config["assumed_sizes"]["kda_low_rank"],
        experts_held=config["n_routed_experts"], experts_first=0,
        n_shared=config["n_shared_experts"])


def _overrides(sizes: dict) -> dict:
    return dict(vocab_size=sizes["vocab"], d_model=sizes["d_model"],
                n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
                n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
                ffn_dim=sizes["ffn"], norm_eps=sizes["norm_eps"],
                n_experts=sizes["n_experts"], moe_top_k=sizes["top_k"],
                expert_dim=sizes["expert_dim"],
                full_attn_every=sizes["full_attn_every"],
                linear_heads=sizes["linear_heads"],
                linear_key_dim=sizes["linear_key_dim"],
                linear_value_dim=sizes["linear_value_dim"],
                linear_conv=sizes["linear_conv"],
                linear_rank=sizes["linear_rank"],
                experts_held=sizes["experts_held"],
                experts_first=sizes["experts_first"],
                n_shared_experts=sizes["n_shared"])


def build_server(config: dict, seed: int, rehearse: bool = False):
    """`LLMServer` as a deployment would build it (it makes the hybrid cache
    from the model's schema), except that the weights come from ONE jitted
    initialiser on the device, in the type they are served in."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    group = config["rehearsal"] if rehearse else config
    engine = {k: v for k, v in {**config["engine"], **group["engine"]}.items()
              if k != "why"}
    overrides = _overrides(model_sizes(config, rehearse))
    llm_cfg = LLMConfig(preset=group["preset"], model_overrides=overrides,
                        param_dtype="bfloat16", seed=seed & 0x7FFFFFFF,
                        **engine)
    model_cfg = getattr(LlamaConfig, group["preset"])(
        max_seq_len=engine["max_seq_len"], param_dtype=jnp.bfloat16,
        **overrides)
    dummy = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda key: Llama(model_cfg).init(key, dummy))(
        seed_key(seed))
    jax.block_until_ready(params)
    return LLMServer(llm_cfg, params=params)


def warm_shapes(server, want: dict) -> list:
    """After the deployment's own list. This engine stops a prompt's prefill
    at its last page boundary, so the chunk before it is short, and its
    bucket is one the harness's (chunk + bucket)-token prompts do not reach
    past 64 tokens (they split into 64-token pieces): one cold prompt a
    bucket from 128 to the chunk, each of chunk + bucket + 1 tokens (a first
    chunk, a continuation of `bucket` tokens that ends on the boundary, the
    state's save, a one-token tail). The harness draws every warm prompt
    afresh, so none of them resumes from a snapshot; the two copy programs
    (state into a snapshot, snapshot into a slot) are compiled and run by
    `LLMServer`'s constructor, a resumed prompt's chunks are the
    continuation programs these requests warm, and the ramp's 16 requests
    (each a turn on a session that set-up opened) take the whole path before
    the window opens."""
    cfg = server.config
    chunk = cfg.prefill_chunk
    shapes = [(chunk + b + 1, 1)
              for b in (128 << i for i in range(20)) if b <= chunk]
    return [(p, n) for p, n in shapes if p + n <= cfg.max_seq_len]
