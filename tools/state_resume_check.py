"""On the chip, at the published widths: a prompt that RESUMES from a state
snapshot at a long context against the plain reference's full forward pass.

    python3 tools/state_resume_check.py --seed 7 --context 16500 --new 1500

Builds `solar-open2-250b-serve` as the benchmark does (its builder, its
engine settings, weights from the seed), in this one process. A first prompt
of `--context` tokens is prefilled cold (it saves a snapshot at its last page
boundary); a second prompt, the first plus `--new` tokens, then resumes from
that snapshot: its logprobs of 9 generated tokens are compared, under the
configuration's own `check`, with
`perfbench/references/solar_open2.py` run over the WHOLE second prompt from
nothing (float32, the token recurrence, in blocks), beside the reference on
weights rounded to float8_e4m3fn against itself (what a lower precision
reads). Prints one JSON line. The benchmark's `check` judges cold prompts
after the window; this is the resumed path's own comparison, by the builder.
"""

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


async def run(args) -> dict:
    import jax
    import numpy as np

    from perfbench import loader, stats
    bench = loader.benchmark()
    config = loader.config_of(bench, "solar-open2-250b-serve")
    builder = loader.module("builders", config["builder"])
    reference = loader.reference_of(config)
    sizes = builder.model_sizes(config, args.rehearse)
    t0 = time.monotonic()
    srv = builder.build_server(config, args.seed, args.rehearse)
    built_s = time.monotonic() - t0
    rng = np.random.default_rng([args.seed, 5])
    first = rng.integers(0, sizes["vocab"], args.context).tolist()
    second = first + rng.integers(0, sizes["vocab"], args.new).tolist()
    page = srv.config.page_size
    try:
        t0 = time.monotonic()
        await srv.generate(first, max_tokens=1)
        cold_s = time.monotonic() - t0
        before = srv.stats()
        t0 = time.monotonic()
        out = await srv.generate(second, max_tokens=9, logprobs=True)
        resumed_s = time.monotonic() - t0
        after = srv.stats()
        got = np.asarray(out["logprobs"], np.float64)
        t0 = time.monotonic()
        want = np.asarray(reference.logprobs_of(
            srv.params, second + out["tokens"], sizes, 9), np.float64)
        reference_s = time.monotonic() - t0
        low = np.asarray(reference.logprobs_of(
            srv.params, second + out["tokens"], sizes, 9,
            weights_as="float8_e4m3fn"), np.float64)
        errs = np.abs(got - want)
        # judged as the benchmark judges a cold prompt: the configuration's
        # own `check` (its reason is in the file), on 9 tokens
        tol = loader.check_of(config, sizes)
        agrees = stats.logprobs_agree(
            {"abs_logprob_errs": errs.tolist(), "finite": bool(
                np.isfinite(got).all())}, tol)
        fails = not stats.logprobs_agree(
            {"abs_logprob_errs": np.abs(low - want).tolist(), "finite": True},
            tol)
        return {
            "agrees_with_reference": bool(agrees), "tolerance": tol,
            "control_fails_as_it_should": bool(fails),
            "device": jax.devices()[0].device_kind, "seed": args.seed,
            "context": args.context, "new": args.new,
            "resumed_from_tokens": (after["prefix_hit_tokens"]
                                    - before["prefix_hit_tokens"]),
            "expected_boundary": (args.context - 1) // page * page,
            "snapshot_hits": (after["state"]["snapshot_hits"]
                              - before["state"]["snapshot_hits"]),
            "abs_logprob_errs": [round(float(e), 4) for e in errs],
            "median_abs_logprob_err": float(np.median(errs)),
            "max_abs_logprob_err": float(errs.max()),
            "control_float8_median": float(np.median(np.abs(low - want))),
            "control_float8_max": float(np.abs(low - want).max()),
            "built_s": built_s, "cold_prefill_s": cold_s,
            "resumed_request_s": resumed_s, "reference_s": reference_s,
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices())}
    finally:
        srv.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--context", type=int, default=16500)
    ap.add_argument("--new", type=int, default=1500)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: the control flow only")
    args = ap.parse_args()
    print(json.dumps(asyncio.run(run(args))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
