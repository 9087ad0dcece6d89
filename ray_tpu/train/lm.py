"""The language-model train step bench.py times and chip_smoke.py checks —
one definition, so what is proven to start on the chip is what is measured.
"""


def make_lm_train_step(cfg, optimizer, key, *, mesh=None, loss_chunk: int = 512):
    """Build (params, opt_state, train_step) for a Llama-family `cfg`.

    `train_step(params, opt_state, tokens[B, T+1]) -> (params, opt_state,
    loss)` is one jitted, donated fwd+bwd+update with the lm_head fused into
    `chunked_cross_entropy` (never materializes [B, T, V]).

    With `mesh`, params and optimizer state are born sharded by
    `llama_rules()` — no full tree is ever staged on one device — and the
    step keeps them so; feed tokens placed with `data_sharding(mesh)` and
    call (or lower) the step under `jax.set_mesh(mesh)`, which is also what
    lets the flash kernel run per shard.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import Llama
    from ray_tpu.ops.losses import chunked_cross_entropy

    model = Llama(cfg)
    dummy = jnp.zeros((2, 8), jnp.int32)

    def loss_fn(params, tokens):
        hidden, _ = model.apply(params, tokens[:, :-1], return_hidden=True)
        w_head = params["params"]["lm_head"]["kernel"]
        loss, _ = chunked_cross_entropy(
            hidden, w_head, tokens[:, 1:],
            chunk_size=min(loss_chunk, tokens.shape[1] - 1))
        return loss

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    if mesh is None:
        params = model.init(key, dummy)
        opt_state = optimizer.init(params)
        return params, opt_state, jax.jit(step, donate_argnums=(0, 1))

    from ray_tpu.parallel.sharding import llama_rules
    rules = llama_rules()
    param_sh = rules.tree_shardings(jax.eval_shape(model.init, key, dummy), mesh)
    params = jax.jit(model.init, out_shardings=param_sh)(key, dummy)
    opt_sh = rules.tree_shardings(jax.eval_shape(optimizer.init, params), mesh)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_sh)(params)
    return params, opt_state, jax.jit(
        step, donate_argnums=(0, 1), out_shardings=(param_sh, opt_sh, None))
