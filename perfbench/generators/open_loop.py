"""Open loop of independent users: Poisson arrivals with a burst, lognormal
prompt and output lengths, nothing shared between prompts. Parameters come
from the mix's file; see `perfbench/README.md`."""

from perfbench import sampling

KIND = "serve"


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    due = sampling.arrivals(seconds, params["rate_rps"], params["burst"],
                            sampling.rng_of(seed, 1))
    n = len(due)
    prompts = sampling.lognormal_ints(n, params["prompt"],
                                      sampling.rng_of(seed, 2))
    outputs = sampling.lognormal_ints(n, params["output"],
                                      sampling.rng_of(seed, 3))
    ids = sampling.rng_of(seed, 4)
    requests = [{"rid": i, "due_s": due[i], "max_tokens": outputs[i],
                 "prompt": sampling.token_ids(prompts[i], model["vocab"], ids),
                 "kind": "fresh"} for i in range(n)]
    return {"mode": "open", "setup": [], "requests": requests,
            "warm": {"prompt_min": params["prompt"]["min"],
                     "prompt_max": params["prompt"]["max"], "sharing": False},
            "check_prompt_lens": params["check_prompt_lens"]}
