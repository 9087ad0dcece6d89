"""Builder for configurations that run through `ray_tpu.models.llama`
(`LlamaConfig`): Mistral, Mixtral. Everything here runs INSIDE the actor that
holds the chip; the parent process never imports this file's jax.

A configuration file names its builder; a model that is not a `LlamaConfig`
brings a builder file of its own with the same three functions (and, where
its parameter tree is not this one, a plain reference of its own):

- `model_sizes(config, rehearse)` -> plain dict of sizes (no jax), which the
  yardstick's arithmetic and the reference read
- `build_server(config, seed, rehearse)` -> an `LLMServer`, weights on the device
- `build_train(config, seed, chips, rehearse)` -> (params, opt_state, step, mesh,
  place, model_cfg)
"""


def model_sizes(config: dict, rehearse: bool = False) -> dict:
    """The configuration's sizes under the benchmark's own names, from the
    published keys of its file (or the `tiny` stand-ins for a rehearsal)."""
    if rehearse:
        moe = config.get("num_local_experts", 0) > 0
        return dict(vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                    head_dim=16, ffn=128, n_experts=4 if moe else 0,
                    top_k=2, rope_theta=10000.0, norm_eps=1e-5)
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn=config["intermediate_size"],
        n_experts=config.get("num_local_experts", 0),
        top_k=config.get("num_experts_per_tok", 2),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]))


def _overrides(sizes: dict) -> dict:
    return dict(vocab_size=sizes["vocab"], d_model=sizes["d_model"],
                n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
                n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
                ffn_dim=sizes["ffn"], rope_theta=sizes["rope_theta"],
                norm_eps=sizes["norm_eps"], n_experts=sizes["n_experts"],
                moe_top_k=sizes["top_k"])


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 (a plain
    `PRNGKey(seed)` overflows int32 there). The seed reaches jit as data, so
    one compiled initialiser serves every seed."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def build_server(config: dict, seed: int, rehearse: bool = False):
    """`LLMServer` as a deployment would build it, except that the weights
    come from ONE jitted initialiser on the device, in the type they are
    served in (the constructor's own `model.init` runs operation by
    operation)."""
    import dataclasses

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
    if model_cfg.n_experts > 0:  # init shapes do not depend on capacity
        model_cfg = dataclasses.replace(
            model_cfg, capacity_factor=model_cfg.n_experts / model_cfg.moe_top_k)
    dummy = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda key: Llama(model_cfg).init(key, dummy))(
        seed_key(seed))
    jax.block_until_ready(params)
    return LLMServer(llm_cfg, params=params)


def build_train(config: dict, seed: int, chips: int, rehearse: bool = False):
    """The pre-training step of the configuration's `train` group through
    `train/lm.py make_lm_train_step`, under an fsdp mesh over `chips`.
    Returns (params, opt_state, step, mesh, place, model_cfg)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.sharding import data_sharding
    from ray_tpu.train.lm import make_lm_train_step

    group = config["rehearsal"] if rehearse else config
    train = group["train"]
    model_cfg = getattr(LlamaConfig, group["preset"])(
        max_seq_len=train["seq_len"], param_dtype=jnp.float32,
        remat=train["remat"], attn_impl="xla" if rehearse else "flash",
        **_overrides(model_sizes(config, rehearse)))
    mesh = make_mesh({"fsdp": chips}) if chips > 1 else None
    params, opt_state, step = make_lm_train_step(
        model_cfg, optax.adamw(train["learning_rate"]), seed_key(seed),
        mesh=mesh)
    sharding = data_sharding(mesh) if mesh is not None else None

    def place(batch):
        return jax.device_put(batch, sharding)

    return params, opt_state, step, mesh, place, model_cfg
