"""Median time a first token spends between the engine and the client: the
client's arrival stamp minus the stamp the deployment took when the engine
yielded it (both `time.monotonic()` on one machine). The serve handle, the
replica's streaming generator and the object plane are what lies between."""

from perfbench import stats


def read(run: dict, args: dict):
    engine = run["engine"]["first_token"]
    t_open = run["edges"]["t_open"]
    values = [(r["token_times"][0] - engine[r["rid"]][1]) * 1e3
              for r in stats.answered(run["requests"])
              if r["due"] >= t_open and r["rid"] in engine]
    return stats.percentile(values, 50) if values else None
