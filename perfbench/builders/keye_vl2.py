"""Builder for `keye-vl2-30b-a3b-serve`: Keye-VL-2.0-30B-A3B's language model
through `ray_tpu.models.llama` (`LlamaConfig.keye_vl2_30b_a3b`: q/k norm,
rotary sections, 128 experts of 768 through the grouped product, the
lightning indexer and its third cache pool). Everything here runs INSIDE the
actor that holds the chip; the parent process never imports this file's jax.
The same three functions as `llama_family.py`, less `build_train` (the
configuration serves), plus `warm_shapes`.
"""

from perfbench.builders.llama_family import seed_key

REHEARSAL = dict(vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 head_dim=16, ffn=128, n_experts=16, top_k=2, expert_dim=32,
                 rope_theta=10000.0, norm_eps=1e-6, rope_sections=(2, 3, 3),
                 index_heads=2, index_dim=8, index_topk=16)


def model_sizes(config: dict, rehearse: bool = False) -> dict:
    """The configuration's sizes under the benchmark's own names, from the
    published keys of its file (or the `keye_tiny` stand-ins for a
    rehearsal). `ffn` is the config's `intermediate_size`, which no layer
    uses; an expert is `expert_dim` wide."""
    if rehearse:
        return dict(REHEARSAL)
    sa = config["sa_config"]
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn=config["intermediate_size"],
        n_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        rope_sections=tuple(config["rope_scaling"]["mrope_section"]),
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"])


def _overrides(sizes: dict) -> dict:
    return dict(vocab_size=sizes["vocab"], d_model=sizes["d_model"],
                n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
                n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
                ffn_dim=sizes["ffn"], rope_theta=sizes["rope_theta"],
                norm_eps=sizes["norm_eps"], n_experts=sizes["n_experts"],
                moe_top_k=sizes["top_k"], expert_dim=sizes["expert_dim"],
                qk_norm=True, rope_sections=tuple(sizes["rope_sections"]),
                index_heads=sizes["index_heads"], index_dim=sizes["index_dim"],
                index_topk=sizes["index_topk"])


def build_server(config: dict, seed: int, rehearse: bool = False):
    """`LLMServer` as a deployment would build it (it makes the three-pool
    cache from the model's schema), except that the weights come from ONE
    jitted initialiser on the device, in the type they are served in."""
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
    """After the deployment's own list (a first chunk, every bucket of the
    continuation program, the decode chunks 1 to `decode_chunk`): one request
    that takes those programs past `index_topk` keys of context, where the
    selection stops being everything, so that the window's first long row is
    not the first. The context a program sees is data (the key blocks are a
    loop with a dynamic bound), so long contexts add no program."""
    cfg, topk = server.config, server.model_cfg.index_topk
    n_prompt = topk + cfg.prefill_chunk + cfg.page_size // 2
    n_out = 2 * cfg.decode_chunk
    return ([(n_prompt, n_out)]
            if n_prompt + n_out <= cfg.max_seq_len else [])
