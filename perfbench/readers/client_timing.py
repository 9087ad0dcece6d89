"""A percentile of a client-side timing over the window's requests.
args: quantity = "ttft_ms" (first token - due) or "lateness_ms" (sent - due);
p = the percentile."""

from perfbench import stats


def read(run: dict, args: dict):
    timing = getattr(stats, args["quantity"])
    t_open = run["edges"]["t_open"]
    values = [timing(r) for r in stats.answered(run["requests"])
              if r["due"] >= t_open]
    return stats.percentile(values, args["p"]) if values else None
