"""Sessions over documents: each request is a question on a document. In
every block of `block` requests exactly `new_per_block` open a new document
(a miss: the whole prompt is prefilled) and the others ask a further question
on one of the `recent` most recent documents (a hit on the prefix cache unless
the document was evicted). The first `recent` documents are opened during
set-up, which is set-up this traffic needs. Open loop, Poisson arrivals."""

from perfbench import sampling

KIND = "serve"


def plan(params: dict, seed: int, seconds: float, model: dict) -> dict:
    due = sampling.arrivals(seconds, params["rate_rps"], params["burst"],
                            sampling.rng_of(seed, 1))
    n, recent, block = len(due), params["recent"], params["block"]
    order = sampling.rng_of(seed, 5)
    is_new = []
    while len(is_new) < n:
        marks = [i < params["new_per_block"] for i in range(block)]
        is_new += order.permutation(marks).tolist()
    n_docs = recent + sum(is_new[:n])
    doc_lens = sampling.lognormal_ints(n_docs, params["document"],
                                       sampling.rng_of(seed, 2), block=8)
    q_lens = sampling.uniform_ints(recent + n, params["question"]["min"],
                                   params["question"]["max"], sampling.rng_of(seed, 6))
    outputs = sampling.lognormal_ints(n, params["output"], sampling.rng_of(seed, 3))
    ids = sampling.rng_of(seed, 4)
    docs = [sampling.token_ids(m, model["vocab"], ids) for m in doc_lens]

    def question(i):
        return sampling.token_ids(q_lens[i], model["vocab"], ids)

    setup = [{"rid": -1 - d, "due_s": None, "max_tokens": 1,
              "prompt": docs[d] + question(d), "kind": "open"}
             for d in range(recent)]
    # a further question comes only on a document whose first answer has had
    # time to arrive: opened at least `settle_requests` requests ago
    requests, newest, opened_at = [], recent - 1, {}
    for i in range(n):
        if is_new[i]:
            newest += 1
            opened_at[newest] = i
            doc, kind = newest, "miss"
        else:
            ready = [d for d in range(newest - recent + 1, newest + 1)
                     if i - opened_at.get(d, -10**9) >= params["settle_requests"]]
            doc, kind = ready[int(order.integers(0, len(ready)))], "hit"
        requests.append({"rid": i, "due_s": due[i], "max_tokens": outputs[i],
                         "prompt": docs[doc] + question(recent + i),
                         "kind": kind, "doc": doc})
    return {"mode": "open", "setup": setup, "requests": requests,
            "warm": {"prompt_min": params["document"]["min"],
                     "prompt_max": params["document"]["max"]
                     + params["question"]["max"], "sharing": True},
            "check_prompt_lens": params["check_prompt_lens"]}
