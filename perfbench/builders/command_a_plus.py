"""Builder for `command-a-plus-serve`: Command A+ (command-a-plus-05-2026) as
one chip's share through `ray_tpu.models.llama`
(`LlamaConfig.command_a_plus`: three sliding-window layers with interleaved
rotary to one full layer without positions, their keys and values in two
pools of pages under the one page manager; a parallel block under a
mean-centred norm; a sigmoid router over all 128 experts with 8 a token, of
which this chip's bank holds 16, beside four shared experts that are
averaged; a slice of the tied vocabulary). Everything here runs INSIDE the
actor that holds the chip; the parent process never imports this file's jax.
The same functions as `solar_open2.py`.
"""

import os

from perfbench.builders.llama_family import seed_key

_PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "models", "llama.py")

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}

REHEARSAL = dict(vocab=256, d_model=64, n_layers=8, n_heads=8, n_kv_heads=2,
                 head_dim=16, ffn=32, n_experts=16, top_k=2, expert_dim=32,
                 norm_eps=1e-5, experts_held=4, experts_first=0, n_shared=2,
                 window=16, rope_theta=50000.0, logit_scale=1.0,
                 layer_types=("sliding", "sliding", "sliding", "full"))


def model_sizes(config: dict, rehearse: bool = False) -> dict:
    """The configuration's sizes under the benchmark's own names, from the
    keys of its file (or the `command_tiny` stand-ins for a rehearsal).
    `n_experts` is what the router scores, `experts_held` what the chip's
    bank holds; `vocab` is the slice; `ffn` is the config's
    `intermediate_size`, an expert's width."""
    with open(_PROGRAM) as f:
        if "layer_types" not in f.read():
            # a checkout from before the program had such layers: say so at
            # once, in the parent process, before any worker or chip is taken
            raise SystemExit(
                f"this checkout's ray_tpu cannot run {config.get('name')!r}: "
                f"its model has no sliding-window layers ({_PROGRAM} knows "
                f"no `layer_types`)")
    if rehearse:
        return dict(REHEARSAL)
    n = config["num_hidden_layers"]
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=n, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn=config["intermediate_size"],
        n_experts=config["num_experts_scored"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["intermediate_size"],
        norm_eps=float(config["layer_norm_eps"]),
        experts_held=config["num_experts"], experts_first=0,
        n_shared=config["num_shared_experts"],
        window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        logit_scale=float(config["logit_scale"]),
        layer_types=tuple(KINDS[k] for k in config["layer_types"][:n]))


def _overrides(sizes: dict) -> dict:
    return dict(vocab_size=sizes["vocab"], d_model=sizes["d_model"],
                n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
                n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
                ffn_dim=sizes["ffn"], norm_eps=sizes["norm_eps"],
                n_experts=sizes["n_experts"], moe_top_k=sizes["top_k"],
                expert_dim=sizes["expert_dim"],
                experts_held=sizes["experts_held"],
                experts_first=sizes["experts_first"],
                n_shared_experts=sizes["n_shared"],
                sliding_window=sizes["window"],
                rope_theta=sizes["rope_theta"],
                logit_scale=sizes["logit_scale"],
                layer_types=tuple(sizes["layer_types"]))


def build_server(config: dict, seed: int, rehearse: bool = False):
    """`LLMServer` as a deployment would build it (it makes both pools from
    the model's schema), except that the weights come from ONE jitted
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
    """After the deployment's own list (first chunks at every bucket,
    continuations at every bucket, a full middle chunk, the decode chunks):
    the programs are the same whether or not a row has passed its window (the
    window is a value of the kernels' walk, not a shape), so what is added
    is one request that takes every path PAST it before the window opens: a
    prompt of a window and two chunks (continuation chunks whose first key
    block lies behind the window, window pages handed back while it
    prefills) with enough decode steps to hand back a page while decoding."""
    cfg = server.config
    past = server.model_cfg.sliding_window + 2 * cfg.prefill_chunk + 16
    n_out = 2 * cfg.decode_chunk + cfg.page_size
    return [(past, n_out)] if past + n_out <= cfg.max_seq_len else []
