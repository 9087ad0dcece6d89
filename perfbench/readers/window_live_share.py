"""Bytes the live rows hold in both pools of a model with sliding-window
layers over what they would hold if every layer kept every key (%), as the
mean of the readings at the window's two edges (`stats()["window"]`'s live
page counts through `counts/window_cache.py`). None on a program without a
window pool, or with no row live at either edge."""

from perfbench.counts import window_cache


def read(run: dict, args: dict):
    shares = []
    for edge in ("open", "close"):
        w = run["counters"][edge]["stats"].get("window")
        if not w:
            return None
        page = w["page_size"]
        one = window_cache.one_pool_bytes(w["full_pages_live"], page,
                                          run["sizes"])
        if one:
            shares.append(100.0 * window_cache.live_bytes(
                w["window_pages_live"], w["full_pages_live"], page,
                run["sizes"]) / one)
    return sum(shares) / len(shares) if shares else None
