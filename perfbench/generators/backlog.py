"""An offline job: one seeded list of requests, offered with a fixed number
in flight so that a free slot always has a request waiting (closed loop,
saturated). A short `ramp` of requests with staggered output lengths goes
first; the window opens when every one of them has completed, so that every
slot has turned over and no two slots run in lock step."""

from perfbench import sampling

KIND = "serve"


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    ramp, ids = params["ramp"], sampling.rng_of(seed, 4)
    n_ramp = ramp["requests"]
    ramp_out = [round(ramp["output_min"] + i * (ramp["output_max"] - ramp["output_min"])
                      / max(n_ramp - 1, 1)) for i in range(n_ramp)]
    n = int(params["requests_per_second_ceiling"] * seconds) + params["in_flight"]
    prompts = sampling.lognormal_ints(n_ramp + n, params["prompt"],
                                      sampling.rng_of(seed, 2))
    outputs = ramp_out + sampling.lognormal_ints(
        n, params["output"], sampling.rng_of(seed, 3))
    requests = [{"rid": i, "due_s": None, "max_tokens": outputs[i],
                 "prompt": sampling.token_ids(prompts[i], model["vocab"], ids),
                 "kind": "ramp" if i < n_ramp else "fresh"}
                for i in range(n_ramp + n)]
    return {"mode": "closed", "in_flight": params["in_flight"], "setup": [],
            "ramp": n_ramp, "requests": requests,
            "warm": {"prompt_min": params["prompt"]["min"],
                     "prompt_max": params["prompt"]["max"], "sharing": False},
            "check_prompt_lens": params["check_prompt_lens"]}
