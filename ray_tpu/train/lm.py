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

    The head under a mesh: where `lm_head`'s rows are sharded over a data
    axis (fsdp), the loss runs per shard through
    `parallel.sharding.rows_gathered_once`: ONE `all_gather` of the head
    before the loss loops (bf16 on the wire, asynchronous in the compiled
    step: `async-collective-start` over `all-gather bf16[D, V]` in the entry
    computation), the gradient summed over the chunks on the chip in f32,
    and ONE f32 `reduce-scatter` to the parameter's shard after the backward
    loop (`reduce-scatter f32[D/n, V]`, `jit(step)/transpose(jvp())/
    shard_map/reduce_scatter`). Neither `while` body holds a collective over
    an array with the vocabulary in it; tests/test_train_head_collectives.py
    reads that from the step compiled for `v5e:2x2`. Left to the
    partitioner the same loss gathers the head 2 x T/chunk times a step and
    reduce-scatters its gradient T/chunk times (see `chunked_cross_entropy`).
    Without a mesh nothing is wrapped and the program is the plain one.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import Llama
    from ray_tpu.ops.losses import chunked_cross_entropy
    from ray_tpu.parallel.sharding import llama_rules, rows_gathered_once

    model = Llama(cfg)
    dummy = jnp.zeros((2, 8), jnp.int32)
    if mesh is not None:
        rules = llama_rules()
        param_sh = rules.tree_shardings(
            jax.eval_shape(model.init, key, dummy), mesh)

    def head_loss(hidden, w_head, labels):
        return chunked_cross_entropy(
            hidden, w_head, labels,
            chunk_size=min(loss_chunk, labels.shape[1]))[0]

    def loss_fn(params, tokens):
        hidden, _ = model.apply(params, tokens[:, :-1], return_hidden=True)
        w_head = params["params"]["lm_head"]["kernel"]
        loss = head_loss
        if mesh is not None:
            loss = rows_gathered_once(
                head_loss, mesh, param_sh["params"]["lm_head"]["kernel"].spec,
                hidden.shape[0])
        return loss(hidden, w_head, tokens[:, 1:])

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    if mesh is None:
        params = model.init(key, dummy)
        opt_state = optimizer.init(params)
        return params, opt_state, jax.jit(step, donate_argnums=(0, 1))

    params = jax.jit(model.init, out_shardings=param_sh)(key, dummy)
    opt_sh = rules.tree_shardings(jax.eval_shape(optimizer.init, params), mesh)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_sh)(params)
    return params, opt_state, jax.jit(
        step, donate_argnums=(0, 1), out_shardings=(param_sh, opt_sh, None))
