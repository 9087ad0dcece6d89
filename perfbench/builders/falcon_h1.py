"""Builder for `falcon-h1-34b-serve`: Falcon-H1-34B-Instruct cut by depth
alone, through `ray_tpu.models.llama` (`LlamaConfig.falcon_h1_34b`: in every
block 20 query heads over 4 kv heads beside a Mamba-2 mixer, both on one norm
and summed, so every layer has pages AND a state a slot in the paged cache;
the published muP multipliers; the whole vocabulary). Everything here runs
INSIDE the actor that holds the chip; the parent process never imports this
file's jax. The same functions as `solar_open2.py`, and `weight_scales` /
`seeded_params`: the standard deviation each matrix is drawn with.
"""

import math
import os

from perfbench.builders.llama_family import seed_key

_PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "ray_tpu", "ops", "ssd.py")

REHEARSAL = dict(vocab=256, d_model=64, n_layers=2, n_heads=5, n_kv_heads=1,
                 head_dim=16, ffn=128, norm_eps=1e-5, rope_theta=1e11,
                 ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_groups=2,
                 ssm_conv=4, ssm_chunk=8, n_experts=0, top_k=0)


def model_sizes(config: dict, rehearse: bool = False) -> dict:
    """The configuration's sizes under the benchmark's own names, from the
    keys of its file (or the `falcon_h1_tiny` stand-ins for a rehearsal, which
    keep the published multipliers)."""
    if not os.path.exists(_PROGRAM):
        # a checkout from before the program had such a mixer: say so at once,
        # in the parent process, before any worker or chip is taken
        raise SystemExit(
            f"this checkout's ray_tpu cannot run {config.get('name')!r}: it "
            f"has no state-space mixer ({_PROGRAM} is not there)")
    multipliers = dict(
        embedding=config["embedding_multiplier"],
        lm_head=config["lm_head_multiplier"],
        attention_in=config["attention_in_multiplier"],
        key=config["key_multiplier"],
        attention_out=config["attention_out_multiplier"],
        ssm_in=config["ssm_in_multiplier"],
        ssm=list(config["ssm_multipliers"]),
        ssm_out=config["ssm_out_multiplier"],
        mlp_gate=config["mlp_multipliers"][0],
        mlp_down=config["mlp_multipliers"][1])
    if rehearse:
        return dict(REHEARSAL, multipliers=multipliers)
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn=config["intermediate_size"],
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        ssm_conv=config["mamba_d_conv"], ssm_chunk=config["mamba_chunk_size"],
        n_experts=0, top_k=0, multipliers=multipliers)


def _overrides(sizes: dict) -> dict:
    m = sizes["multipliers"]
    return dict(vocab_size=sizes["vocab"], d_model=sizes["d_model"],
                n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
                n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
                ffn_dim=sizes["ffn"], norm_eps=sizes["norm_eps"],
                rope_theta=sizes["rope_theta"], ssm_heads=sizes["ssm_heads"],
                ssm_head_dim=sizes["ssm_head_dim"],
                ssm_state=sizes["ssm_state"], ssm_groups=sizes["ssm_groups"],
                ssm_conv=sizes["ssm_conv"], ssm_chunk=sizes["ssm_chunk"],
                embed_scale=m["embedding"], logit_scale=m["lm_head"],
                attn_in_scale=m["attention_in"], key_scale=m["key"],
                attn_out_scale=m["attention_out"], ssm_in_scale=m["ssm_in"],
                ssm_scales=tuple(m["ssm"]), ssm_out_scale=m["ssm_out"],
                mlp_gate_scale=m["mlp_gate"], mlp_down_scale=m["mlp_down"])


def weight_scales(sizes: dict) -> dict:
    """The standard deviation each matrix is drawn with, so that WITH the
    published multipliers applied every branch is of the order of the stream
    it is added to (the multipliers were fitted to trained weights: under
    normal(0.02) everywhere the branches come out at thousandths of the
    stream and a check would compare an embedding with itself). From the
    widths, so that the tiny stand-in follows the same rule:

    - embed: the stream starts at RMS 1 (E * embedding);
    - wq, wk, wv: q, k (after `key`) and v at RMS 1 a component, so scores
      q . k / sqrt(D) spread by about 1;
    - wo, out_proj, w_down: a branch's output at RMS about 1/2 on inputs of
      RMS about 1/5 (attention's average of values), 1 (the gated norm's
      output) and 1/2 (silu(gate) * up);
    - in_proj: the projection at RMS 4 before its segment multipliers (z and
      dt then at 1.4, x at 1, B at 0.7, C at 2);
    - conv: the four taps keep their input's RMS; conv_bias 0.1;
    - w_gate (after `mlp_gate`), w_up: RMS 1;
    - lm_head: logits (after `lm_head`) spread by about 2.
    """
    m, d = sizes["multipliers"], sizes["d_model"]
    root = math.sqrt
    attn_in = root(sizes["n_heads"] * sizes["head_dim"])
    ssm_in = root(sizes["ssm_heads"] * sizes["ssm_head_dim"])
    return {
        "embed": 1.0 / m["embedding"],
        "wq": 1.0 / (root(d) * m["attention_in"]),
        "wk": 1.0 / (root(d) * m["attention_in"] * m["key"]),
        "wv": 1.0 / (root(d) * m["attention_in"]),
        "wo": 2.5 / (attn_in * m["attention_out"]),
        "in_proj": 4.0 / (root(d) * m["ssm_in"]),
        "conv": 0.5, "conv_bias": 0.1,
        "out_proj": 0.5 / (ssm_in * m["ssm_out"]),
        "w_gate": 1.0 / (root(d) * m["mlp_gate"]),
        "w_up": 1.0 / root(d),
        "w_down": 1.0 / (root(sizes["ffn"]) * m["mlp_down"]),
        "lm_head": 2.0 / (root(d) * m["lm_head"]),
    }


def seeded_params(model_cfg, seed: int, scales: dict):
    """The model's parameters from `seed` in ONE jitted call on the device:
    `Llama.init` draws every matrix normal(0.02) (and A_log, D, dt_bias and
    the norms' scales as Mamba-2 does); each matrix named in `scales` is then
    brought to its own standard deviation."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama

    def name_of(path):
        keys = [getattr(k, "key", None) for k in path]
        last = keys[-1]
        return keys[-2] if last in ("kernel", "embedding") else last

    def make(key):
        params = Llama(model_cfg).init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map_with_path(
            lambda path, x: (x * (scales[name_of(path)] / 0.02)).astype(x.dtype)
            if name_of(path) in scales else x, params)

    params = jax.jit(make)(seed_key(seed))
    jax.block_until_ready(params)
    return params


def build_server(config: dict, seed: int, rehearse: bool = False):
    """`LLMServer` as a deployment would build it (it makes the cache with a
    state and pages in every layer from the model's schema), on weights from
    the seed at the configuration's scales (`assumed.weight_scale`; the rule
    above at the rehearsal's widths)."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    group = config["rehearsal"] if rehearse else config
    engine = {k: v for k, v in {**config["engine"], **group["engine"]}.items()
              if k != "why"}
    sizes = model_sizes(config, rehearse)
    overrides = _overrides(sizes)
    llm_cfg = LLMConfig(preset=group["preset"], model_overrides=overrides,
                        param_dtype="bfloat16", seed=seed & 0x7FFFFFFF,
                        **engine)
    model_cfg = getattr(LlamaConfig, group["preset"])(
        max_seq_len=engine["max_seq_len"], param_dtype=jnp.bfloat16,
        **overrides)
    scales = (weight_scales(sizes) if rehearse
              else config["assumed"]["weight_scale"]["values"])
    return LLMServer(llm_cfg, params=seeded_params(model_cfg, seed, scales))


def warm_shapes(server, want: dict) -> list:
    """After the deployment's own list. This engine stops a prompt's prefill
    where it leaves the tree and at its last page boundary, so the chunks
    before those stops are short, and their buckets are ones the harness's
    (chunk + bucket)-token prompts do not reach past 64 tokens (they split
    into 64-token pieces): one cold prompt a bucket from 128 to the chunk,
    each of chunk + bucket + 1 tokens (a first chunk, a continuation of
    `bucket` tokens that ends on the boundary, the state's save, a one-token
    tail). The two copy programs (state into a snapshot, snapshot into a
    slot) are compiled and run by `LLMServer`'s constructor, and the ramp's
    requests take the whole resumed path before the window opens."""
    cfg = server.config
    chunk = cfg.prefill_chunk
    shapes = [(chunk + b + 1, 1)
              for b in (128 << i for i in range(20)) if b <= chunk]
    return [(p, n) for p, n in shapes if p + n <= cfg.max_seq_len]
