"""Train: JaxTrainer fitting a tiny Llama with checkpointing.

Run on a CPU box:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/02_train_llama.py
On a TPU host the same script runs the loop in a chip-bound worker:
`ray.init()` counts the chips (the driver itself never imports jax) and
`ScalingConfig(use_tpu=True, chips_per_worker=N)` binds the TrainWorker actor
to N of them. Without `use_tpu` the worker is a CPU worker, whatever the host
has. chip_smoke.py does this at llama_1b width.
"""
import numpy as np

import ray_tpu as ray
from ray_tpu import train

ray.init(num_cpus=2)
CHIPS = int(ray.cluster_resources().get("TPU", 0))


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import Llama, LlamaConfig
    from ray_tpu.ops.losses import cross_entropy

    cfg = LlamaConfig.tiny(max_seq_len=64)
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 33)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s, toks):
        def loss_fn(p):
            logits, _ = model.apply(p, toks[:, :-1])
            return cross_entropy(logits, toks[:, 1:])[0]
        loss, g = jax.value_and_grad(loss_fn)(p)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), s, loss

    for i in range(config.get("steps", 5)):
        params, opt_state, loss = step(params, opt_state, tokens)
        train.session.report({"step": i, "loss": float(loss)})


trainer = train.JaxTrainer(
    train_loop, train_loop_config={"steps": 5},
    scaling_config=train.ScalingConfig(num_workers=1, use_tpu=CHIPS > 0,
                                       chips_per_worker=CHIPS or None),
    run_config=train.RunConfig(name="example-llama"),
)
result = trainer.fit()
print("final metrics:", result.metrics)
ray.shutdown()
