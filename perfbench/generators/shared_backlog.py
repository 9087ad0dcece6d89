"""An offline job over a shared set of long documents: `backlog.py`'s closed
loop (a fixed number in flight, a list that never drains, a ramp first) over
`sessions.py`'s documents (most requests ask a further question on one of the
`recent` most recent documents, a prefix hit on the whole document; a few open
a new one). The first `recent` documents are opened during set-up.

In every block of `block` requests exactly `new_per_block` open a new
document; a document is asked again only once `settle_requests` further
requests have been offered since it was opened, more than are in flight, so
that its pages are in the cache by then. Document lengths are stratified: every
seed has the same set, and the documents opened later come at equal distances. The ramp's requests are questions on set-up documents
with staggered answer lengths; the window opens when all of them are done."""

from perfbench import sampling

KIND = "serve"
NEW_DOC_STRATA = 4


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    ramp, recent, block = params["ramp"], params["recent"], params["block"]
    n_ramp = ramp["requests"]
    n = int(params["requests_per_second_ceiling"] * seconds) + params["in_flight"]
    order = sampling.rng_of(seed, 5)
    # a new document is 32-56 prefill chunks, some 6% of a window's work, and
    # how many of them fall into a window decides its rate: so they come at
    # equal distances (the same place in every block, whatever the seed) and
    # their lengths are stratified in runs of `NEW_DOC_STRATA`, so that any
    # few consecutive ones hold the same work
    at = [block * (j + 1) // (params["new_per_block"] + 1)
          for j in range(params["new_per_block"])]
    is_new = [False] * n_ramp + [i % block in at for i in range(n)]
    doc_lens = (
        sampling.lognormal_ints(recent, params["document"],
                                sampling.rng_of(seed, 2), block=recent)
        + sampling.lognormal_ints(sum(is_new), params["document"],
                                  sampling.rng_of(seed, 7),
                                  block=NEW_DOC_STRATA))
    q_lens = sampling.uniform_ints(recent + n_ramp + n, params["question"]["min"],
                                   params["question"]["max"],
                                   sampling.rng_of(seed, 6))
    ramp_out = [round(ramp["output_min"] + i * (ramp["output_max"] - ramp["output_min"])
                      / max(n_ramp - 1, 1)) for i in range(n_ramp)]
    outputs = ramp_out + sampling.lognormal_ints(n, params["output"],
                                                 sampling.rng_of(seed, 3))
    ids = sampling.rng_of(seed, 4)
    docs = [sampling.token_ids(m, model["vocab"], ids) for m in doc_lens]

    def question(i):
        return sampling.token_ids(q_lens[i], model["vocab"], ids)

    setup = [{"rid": -1 - d, "due_s": None, "max_tokens": 1,
              "prompt": docs[d] + question(d), "kind": "open"}
             for d in range(recent)]
    requests, newest, opened_at = [], recent - 1, {}
    for i in range(n_ramp + n):
        if is_new[i]:
            newest += 1
            opened_at[newest] = i
            doc, kind = newest, "miss"
        else:
            ready = [d for d in range(newest - recent + 1, newest + 1)
                     if i - opened_at.get(d, -10**9) >= params["settle_requests"]]
            if not ready:
                raise ValueError(
                    f"request {i}: none of the {recent} most recent documents "
                    f"has settled; `settle_requests` has to stay under "
                    f"(recent - 1) x block / new_per_block")
            doc = ready[int(order.integers(0, len(ready)))]
            kind = "ramp" if i < n_ramp else "hit"
        requests.append({"rid": i, "due_s": None, "max_tokens": outputs[i],
                         "prompt": docs[doc] + question(recent + i),
                         "kind": kind, "doc": doc})
    return {"mode": "closed", "in_flight": params["in_flight"], "setup": setup,
            "ramp": n_ramp, "requests": requests,
            "warm": {"prompt_min": params["document"]["min"]
                     + params["question"]["min"],
                     "prompt_max": params["document"]["max"]
                     + params["question"]["max"], "sharing": True},
            "check_prompt_lens": params["check_prompt_lens"]}
