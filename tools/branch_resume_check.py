"""On the chip, at the published widths: a prompt that RESUMES from a BRANCH
snapshot (saved where prompts leave a shared system prompt) against the plain
reference's full forward pass. The twin of `state_resume_check.py` for
`falcon-h1-34b-serve`.

    python3 tools/branch_resume_check.py --seed 7 --system 1900 --turn 400

Builds the configuration as the benchmark does (its builder, its engine
settings, weights from the seed), in this one process. Three prompts share a
system prompt of `--system` tokens and go their own way for `--turn` tokens:
the first is cold and saves a snapshot at its own end; the second finds the
system prompt's pages and no state at their end, prefills everything again
and saves where it leaves the tree; the third resumes there. The third's
logprobs of 9 generated tokens are compared, under the configuration's own
`check`, with `perfbench/references/falcon_h1.py` run over its WHOLE prompt
from nothing (float32, the token recurrence), beside the reference on weights
rounded to float8_e4m3fn against itself (what a lower precision reads), and
the reference gives the RMS of each branch against the stream's, a layer at a
time. Prints one JSON line. The benchmark's `check` judges cold prompts after
the window; this is the resumed path's own comparison, by the builder.
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
    config = loader.config_of(bench, "falcon-h1-34b-serve")
    builder = loader.module("builders", config["builder"])
    reference = loader.reference_of(config)
    sizes = builder.model_sizes(config, args.rehearse)
    t0 = time.monotonic()
    srv = builder.build_server(config, args.seed, args.rehearse)
    built_s = time.monotonic() - t0
    rng = np.random.default_rng([args.seed, 5])
    draw = lambda n: rng.integers(0, sizes["vocab"], n).tolist()
    system = draw(args.system)
    prompts = [system + draw(args.turn) for _ in range(3)]
    page = srv.config.page_size
    try:
        for prompt in prompts[:2]:
            await srv.generate(prompt, max_tokens=1)
        before = srv.stats()
        t0 = time.monotonic()
        out = await srv.generate(prompts[2], max_tokens=9, logprobs=True)
        resumed_s = time.monotonic() - t0
        after = srv.stats()
        got = np.asarray(out["logprobs"], np.float64)
        sequence = prompts[2] + out["tokens"]
        t0 = time.monotonic()
        want = np.asarray(reference.logprobs_of(
            srv.params, sequence, sizes, 9), np.float64)
        reference_s = time.monotonic() - t0
        low = np.asarray(reference.logprobs_of(
            srv.params, sequence, sizes, 9, weights_as="float8_e4m3fn"),
            np.float64)
        probe = []
        reference.hidden_states(srv.params, sequence[:args.system], sizes,
                                probe=probe)
        errs = np.abs(got - want)
        tol = loader.check_of(config, sizes)
        agrees = stats.logprobs_agree(
            {"abs_logprob_errs": errs.tolist(), "finite": bool(
                np.isfinite(got).all())}, tol)
        fails = not stats.logprobs_agree(
            {"abs_logprob_errs": np.abs(low - want).tolist(), "finite": True},
            tol)
        grew = lambda key: after["state"][key] - before["state"][key]
        return {
            "agrees_with_reference": bool(agrees), "tolerance": tol,
            "control_fails_as_it_should": bool(fails),
            "device": jax.devices()[0].device_kind, "seed": args.seed,
            "system": args.system, "turn": args.turn,
            "resumed_from_tokens": (after["prefix_hit_tokens"]
                                    - before["prefix_hit_tokens"]),
            "expected_branch": args.system // page * page,
            "branch_snapshots_saved": after["state"]["branch_snapshots_saved"],
            "branch_snapshot_hits": grew("branch_snapshot_hits"),
            "abs_logprob_errs": [round(float(e), 4) for e in errs],
            "median_abs_logprob_err": float(np.median(errs)),
            "max_abs_logprob_err": float(errs.max()),
            "control_float8_median": float(np.median(np.abs(low - want))),
            "control_float8_max": float(np.abs(low - want).max()),
            "rms_by_layer": [{k: round(v, 4) for k, v in row.items()}
                             for row in probe],
            "built_s": built_s, "resumed_request_s": resumed_s,
            "reference_s": reference_s,
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices())}
    finally:
        srv.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--system", type=int, default=1900)
    ap.add_argument("--turn", type=int, default=400)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: the control flow only")
    args = ap.parse_args()
    print(json.dumps(asyncio.run(run(args))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
