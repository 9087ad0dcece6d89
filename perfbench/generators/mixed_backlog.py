"""Chat turns and pasted documents in ONE queue: `backlog.py`'s closed loop (a
fixed number in flight, a list that never drains, a ramp first) over requests
of two kinds. In every block of `block` consecutive requests the ones at the
places `long_places` are LONG (a document of several windows of a
sliding-window model), the others SHORT (a chat turn, wholly inside one
window); each kind has its own lognormal of prompt lengths, both share the
answers' one. Nothing is shared between requests: every prompt's ids are drawn
apart from the vocabulary (the slice the model holds), so the prefix cache is
bypassed. The ramp's requests are the first of the same list with answers
staggered; the window opens when all of them are done, so it opens with long
rows live. Lengths are stratified (`sampling.py`): every seed has the same
set of lengths of each kind, and the seed swaps neighbours; every
`blocks_per_stratum` blocks of requests hold one whole set of strata of each
kind, so any stretch of that many requests carries the same prompts."""

from perfbench import sampling

KIND = "serve"


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    ramp, block = params["ramp"], params["block"]
    long_places = set(params["long_places"])
    n_ramp = ramp["requests"]
    n = int(params["requests_per_second_ceiling"] * seconds) + params["in_flight"]
    total = n_ramp + n
    is_long = [i % block in long_places for i in range(total)]
    # strata a kind such that every `blocks_per_stratum` blocks of requests
    # hold one whole set of each kind's lengths: the long prompts are nearly
    # all of a window's work, and with 32 strata of them a stretch of 40
    # requests carried 217k to 340k prompt tokens (a window a few seconds
    # later read 8% lower: my chip runs, PR 39)
    n_long = len(long_places) * params["blocks_per_stratum"]
    n_short = (block - len(long_places)) * params["blocks_per_stratum"]
    long_lens = iter(sampling.lognormal_ints(
        sum(is_long), params["long_prompt"], sampling.rng_of(seed, 2),
        block=n_long))
    short_lens = iter(sampling.lognormal_ints(
        total - sum(is_long), params["short_prompt"], sampling.rng_of(seed, 7),
        block=n_short))
    ramp_out = [round(ramp["output_min"] + i * (ramp["output_max"] - ramp["output_min"])
                      / max(n_ramp - 1, 1)) for i in range(n_ramp)]
    outputs = ramp_out + sampling.lognormal_ints(
        n, params["output"], sampling.rng_of(seed, 3))
    ids = sampling.rng_of(seed, 4)
    requests = [{"rid": i, "due_s": None, "max_tokens": outputs[i],
                 "prompt": sampling.token_ids(
                     next(long_lens if is_long[i] else short_lens),
                     model["vocab"], ids),
                 "kind": "ramp" if i < n_ramp else
                 ("long" if is_long[i] else "short")}
                for i in range(total)]
    return {"mode": "closed", "in_flight": params["in_flight"], "setup": [],
            "ramp": n_ramp, "requests": requests,
            "warm": {"prompt_min": params["short_prompt"]["min"],
                     "prompt_max": params["long_prompt"]["max"],
                     "sharing": False},
            "check_prompt_lens": params["check_prompt_lens"]}
