"""Agents on long sessions: `backlog.py`'s closed loop (a fixed number in
flight, a list that never drains, a ramp first) over sessions that GROW. The
`sessions` live sessions take turns round robin, so a session's next turn is
offered `sessions` requests after its last, more than are in flight: its
previous prompt has been prefilled, and its pages and (on a model with
recurrent state) its snapshot are in the cache by then. A turn's prompt is the
session's previous prompt plus new text (a tool result, a file); the answer is
not fed back, so the plan is fixed before the run, as every generator's is. A
session whose prompt has passed `retire_past` tokens retires and a new one
opens in its place with a prompt of its own (a miss on all of it).

Set-up opens the live sessions at contexts staggered evenly over
`setup_context`, one request each, so that the window runs in the steady
state: sessions of every age, some retiring. The ramp's requests are the first
turns, with staggered answer lengths; the window opens when all are done.
Lengths are stratified (`sampling.py`): every seed has the same set."""

from perfbench import sampling

KIND = "serve"


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    ramp, live = params["ramp"], params["sessions"]
    n_ramp = ramp["requests"]
    n = int(params["requests_per_second_ceiling"] * seconds) + params["in_flight"]
    total = n_ramp + n
    new_lens = sampling.lognormal_ints(total, params["new_text"],
                                       sampling.rng_of(seed, 2))
    open_lens = sampling.lognormal_ints(total, params["opening"],
                                        sampling.rng_of(seed, 7), block=8)
    ramp_out = [round(ramp["output_min"] + i * (ramp["output_max"] - ramp["output_min"])
                      / max(n_ramp - 1, 1)) for i in range(n_ramp)]
    outputs = ramp_out + sampling.lognormal_ints(n, params["output"],
                                                 sampling.rng_of(seed, 3))
    ids = sampling.rng_of(seed, 4)
    lo, hi = params["setup_context"]["min"], params["setup_context"]["max"]
    contexts = [sampling.token_ids(round(lo + j * (hi - lo) / max(live - 1, 1)),
                                   model["vocab"], ids) for j in range(live)]
    setup = [{"rid": -1 - j, "due_s": None, "max_tokens": 1,
              "prompt": contexts[j], "kind": "open"} for j in range(live)]
    requests, opened = [], 0
    for i in range(total):
        s = i % live
        if len(contexts[s]) > params["retire_past"]:
            contexts[s] = sampling.token_ids(open_lens[opened], model["vocab"], ids)
            opened += 1
            kind = "open"
        else:
            contexts[s] = contexts[s] + sampling.token_ids(
                new_lens[i], model["vocab"], ids)
            kind = "ramp" if i < n_ramp else "turn"
        requests.append({"rid": i, "due_s": None, "max_tokens": outputs[i],
                         "prompt": contexts[s], "kind": kind, "session": s})
    if any(r["kind"] != "ramp" for r in requests[:n_ramp]):
        raise ValueError("a ramp request opens a session: the window would "
                         "wait for a kind of request that never ends it")
    return {"mode": "closed", "in_flight": params["in_flight"], "setup": setup,
            "ramp": n_ramp, "requests": requests,
            "warm": {"prompt_min": params["opening"]["min"],
                     "prompt_max": params["retire_past"]
                     + params["new_text"]["max"], "sharing": True},
            "check_prompt_lens": params["check_prompt_lens"]}
