"""The engine catches its own stalls (ISSUE 37): a watched phase
(`util.tracing.PhaseTotals(watch=...)`) that outlasts its work is counted at
its exit, and a watchdog (`util.tracing.StallWatch`) that lives as long as the
tick loop samples the process while the stall lasts and files one record of
what every thread did meanwhile: `stats()["decode"]["stall_s"|"stall_n"|
"stall_max_s"]`, `stats()["stalls"]`, one WARNING line of JSON.

The `tiny` engine of `tests/test_llm_engine_phases.py`, one for the module on
one event loop. No test waits on an upper bound of the clock: an induced stall
lasts until the watchdog has seen it.
"""

import asyncio
import json
import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu.util import tracing

WAIT_S = 120.0            # a bound on waiting for what must come, never a limit


def _watchdogs():
    return [t for t in threading.enumerate() if t.name == "stall-watch"]


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module")
def srv():
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        preset="tiny", max_batch_slots=2, max_seq_len=64, paged=True,
        page_size=8, num_pages=15, prefill_chunk=16, decode_chunk=4, seed=0))
    yield server
    server.close()


def _generate(srv, loop, n=3, seed=3):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 250, 20 + i).tolist() for i in range(n)]

    async def go():
        return await asyncio.wait_for(asyncio.gather(*[
            srv.generate(p, max_tokens=9) for p in prompts]), WAIT_S)
    return loop.run_until_complete(go())


@pytest.fixture
def samples(monkeypatch):
    """Every sample the watchdog takes, as it took it."""
    taken = []
    sample = tracing._process_sample

    def counting(first):
        taken.append(sample(first))
        return taken[-1]

    monkeypatch.setattr(tracing, "_process_sample", counting)
    return taken


def test_counters_are_there_at_zero_from_construction():
    from ray_tpu.serve.llm import WATCHED_PHASES, LLMConfig, LLMServer
    stats = LLMServer(LLMConfig(preset="tiny", max_batch_slots=2,
                                max_seq_len=32, paged=False)).stats()
    d = stats["decode"]
    assert WATCHED_PHASES == ("decode_sync", "prefill_first_token")
    assert d["stall_s"] == dict.fromkeys(WATCHED_PHASES, 0.0)
    assert d["stall_n"] == dict.fromkeys(WATCHED_PHASES, 0)
    assert d["stall_max_s"] == 0
    assert stats["stalls"] == []
    assert not _watchdogs()          # an idle engine has no such thread


def test_a_run_without_a_stall_files_nothing(srv, loop, samples, caplog):
    with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.llm"):
        outs = _generate(srv, loop)
    assert all(len(o["tokens"]) == 9 for o in outs)
    stats = srv.stats()
    assert sum(stats["decode"]["stall_n"].values()) == 0
    assert stats["stalls"] == [] and not samples
    assert not [r for r in caplog.records if r.name == "ray_tpu.serve.llm"]
    assert not _watchdogs()          # the loop has ended, and its thread


def test_an_induced_stall_is_counted_sampled_filed_and_logged_once(
        srv, loop, samples, caplog, monkeypatch):
    """One `_read_chunk` sleeps 0.6 s, and on until the watchdog has taken
    its first sample: the floor is patched down so that the threshold is
    8 times the mean of a CPU engine's reads."""
    from ray_tpu.serve.llm import LOOP_PHASES
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.05)
    _generate(srv, loop)             # decode_sync has a mean to be judged by
    before = srv.stats()["decode"]
    read_chunk, seen = srv._read_chunk, {}

    def sleeping_read_chunk():
        if not seen:
            seen["thread"] = threading.current_thread().name
            seen["watchdogs"] = len(_watchdogs())
            t0 = time.monotonic()
            time.sleep(0.6)
            while not samples and time.monotonic() - t0 < WAIT_S:
                time.sleep(0.02)
        return read_chunk()

    monkeypatch.setattr(srv, "_read_chunk", sleeping_read_chunk)
    with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.llm"):
        outs = _generate(srv, loop, seed=4)
    assert all(len(o["tokens"]) == 9 for o in outs)
    assert seen["watchdogs"] == 1 and not _watchdogs()
    stats = srv.stats()
    d = stats["decode"]
    assert d["stall_n"]["decode_sync"] - before["stall_n"]["decode_sync"] == 1
    assert d["stall_n"]["prefill_first_token"] == 0
    grown = d["stall_s"]["decode_sync"] - before["stall_s"]["decode_sync"]
    assert grown >= 0.6 and d["stall_max_s"] >= 0.6
    assert set(d["stall_s"]) == set(d["stall_n"]) == {
        "decode_sync", "prefill_first_token"}
    # the stall's seconds are seconds of its phase, and the loop still adds up
    assert grown <= d["phase_s"]["decode_sync"] - before["phase_s"]["decode_sync"]
    top = sum(d["phase_s"][k] for k in LOOP_PHASES)
    assert abs(top - d["loop_s"]) <= 0.02 * d["loop_s"]

    assert len(samples) == 2         # one while it lasted, one at its end
    [record] = stats["stalls"]
    assert record["phase"] == "decode_sync" and record["sampled"] is True
    assert record["dur_s"] >= 0.6 and record["dur_s"] == pytest.approx(grown)
    assert 0 < record["seen_after_s"] <= record["dur_s"]
    assert abs(record["t"] - time.time()) < WAIT_S      # the wall clock's
    frames = record["py_frames"][seen["thread"]]
    assert 1 <= len(frames) <= 4
    assert any(f.endswith(" sleeping_read_chunk") for f in frames), frames
    [row] = [r for r in record["threads"] if r.get("py") == seen["thread"]]
    assert row["state"][0] == "S" and row["cpu_s"] < record["dur_s"]
    assert len(record["threads"]) <= 16
    facts = record["engine"]
    assert facts["active"] >= 1 and facts["ticks"] > 0
    assert set(facts) == {"inflight", "reading_seq", "reading_steps",
                          "first_pending", "active", "queued_prompts",
                          "staged_bytes", "ticks"}
    logged = [r for r in caplog.records if r.name == "ray_tpu.serve.llm"]
    assert len(logged) == 1 and logged[0].levelno == logging.WARNING
    assert json.loads(logged[0].getMessage()) == record   # one line of JSON
    assert "\n" not in logged[0].getMessage()


def test_a_stalled_first_token_counts_under_its_own_key(
        srv, loop, samples, monkeypatch):
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.05)
    _generate(srv, loop)
    before = srv.stats()
    first_token, seen = srv._first_token, {}

    def sleeping_first_token(job, last_logits):
        if not seen:
            seen["t0"] = time.monotonic()
            while not samples and time.monotonic() - seen["t0"] < WAIT_S:
                time.sleep(0.02)
        return first_token(job, last_logits)

    monkeypatch.setattr(srv, "_first_token", sleeping_first_token)
    _generate(srv, loop, seed=5)
    after = srv.stats()
    grew = {k: after["decode"]["stall_n"][k] - before["decode"]["stall_n"][k]
            for k in after["decode"]["stall_n"]}
    assert grew == {"decode_sync": 0, "prefill_first_token": 1}
    for key in ("decode_sync", "prefill_first_token"):      # monotonic
        assert (after["decode"]["stall_s"][key]
                >= before["decode"]["stall_s"][key])
    record = after["stalls"][-1]
    assert len(after["stalls"]) == len(before["stalls"]) + 1
    assert record["phase"] == "prefill_first_token" and record["sampled"]
    assert record["engine"]["reading_seq"] is None    # no chunk being read
    assert record["engine"]["queued_prompts"] >= 1


def test_the_ninth_record_pushes_out_the_first(srv, caplog):
    kept = list(srv._stalls)
    try:
        srv._stalls.clear()
        with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.llm"):
            for i in range(9):
                srv._file_stall({"t": float(i), "phase": "decode_sync",
                                 "dur_s": 1.0, "sampled": False})
        assert [r["t"] for r in srv.stats()["stalls"]] == [
            float(i) for i in range(1, 9)]
        assert len(caplog.records) == 9
    finally:
        srv._stalls.clear()
        srv._stalls.extend(kept)


@pytest.mark.parametrize("how", ["returns", "raises"])
def test_the_watchdog_ends_with_the_tick_loop(srv, loop, monkeypatch, how):
    inner, seen = srv._tick_loop_inner, {}

    async def watched_inner():
        seen["watchdogs"] = len(_watchdogs())
        if how == "raises":
            raise RuntimeError("the device fell over")
        await inner()

    monkeypatch.setattr(srv, "_tick_loop_inner", watched_inner)
    if how == "raises":
        with pytest.raises(RuntimeError):      # the request fails loudly
            _generate(srv, loop, n=1)
        assert "the device fell over" in str(srv._tick_task.exception())
    else:
        _generate(srv, loop, n=1)
    assert seen["watchdogs"] == 1
    assert not _watchdogs()
    assert srv._phases.open is None


def test_phase_sets_and_clears_open_for_a_watched_key():
    tot = tracing.PhaseTotals("engine", ("read", "work"), watch=("read",))
    assert tot.open is None and tot.stall_counts == {"read": 0}
    assert tot.stall_seconds == {"read": 0.0} and tot.stall_max_s == 0.0
    with tracing.phase(tot, "work"):
        assert tot.open is None                   # not a watched key
    with tracing.phase(tot, "read"):
        key, t0, serial = entry = tot.open
        assert key == "read" and t0 <= time.perf_counter()
        with tracing.phase(tot, "work"):
            assert tot.open is entry              # a nested one leaves it
        assert tot.open is entry
    assert tot.open is None
    with pytest.raises(ValueError):
        with tracing.phase(tot, "read"):
            assert tot.open[2] == serial + 1      # each entry its own
            raise ValueError("x")
    assert tot.open is None
    assert tot.counts == {"read": 2, "work": 2}
    with pytest.raises(ValueError):
        tracing.PhaseTotals("engine", ("read",), watch=("no_such_key",))


def _lately(tot, key, seconds, n=None):
    """`n` stretches of `seconds` each as the key's recent past."""
    tot.recent[key].extend([seconds] * (n or tracing.STALL_RECENT))


def test_a_stall_is_over_the_floor_and_over_eight_recent_means(monkeypatch):
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.01)
    tot = tracing.PhaseTotals("engine", ("read",), watch=("read",))
    assert tot.stall_threshold("read") is None
    with tracing.phase(tot, "read"):              # the first has no mean
        time.sleep(0.03)
    assert tot.stall_counts["read"] == 0
    assert tot.stall_threshold("read") >= 8 * 0.03
    with tracing.phase(tot, "read"):              # over the floor, under 8x
        time.sleep(0.03)
    assert tot.stall_counts["read"] == 0 and not tot.stalled
    _lately(tot, "read", 0.001)
    assert tot.stall_threshold("read") == pytest.approx(0.01)
    counted = tot.counts["read"]
    with tracing.phase(tot, "read", entries=0):   # a further stretch, too
        time.sleep(0.03)
    assert tot.stall_counts["read"] == 1 and tot.counts["read"] == counted
    [(key, t0, dt, limit)] = tot.stalled
    assert key == "read" and dt >= 0.03 and limit == pytest.approx(0.01)
    assert tot.stall_seconds["read"] == tot.stall_max_s == dt
    assert tot.recent["read"][-1] == dt           # a stall is an entry too
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 10.0)
    with tracing.phase(tot, "read"):              # under the floor
        time.sleep(0.03)
    assert tot.stall_counts["read"] == 1


def test_the_threshold_forgets_what_set_up_took(monkeypatch):
    """Set-up's long honest reads (a compile, a first token behind a whole
    prompt) leave the mean once STALL_RECENT entries have come after them:
    the window's stalls are judged by the window's reads."""
    tot = tracing.PhaseTotals("engine", ("read",), watch=("read",))
    for dt in (15.0, 1.1, 1.4, 3.9):              # seconds, as set-up reads
        tot.recent["read"].append(dt)
    long_ago = tot.stall_threshold("read")
    assert long_ago > 8 * 5
    for i in range(tracing.STALL_RECENT):
        with tracing.phase(tot, "read"):
            pass
        assert tot.stall_threshold("read") <= long_ago    # never grows
    assert tot.stall_counts["read"] == 0
    assert tot.stall_threshold("read") == tracing.STALL_FLOOR_S
    assert len(tot.recent["read"]) == tracing.STALL_RECENT
    assert tot.seconds["read"] < 1.0              # the lifetime's own count
    # and after a change of what is honest the mean follows: of a run of
    # long reads only the first few are called stalls
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.001)
    _lately(tot, "read", 0.0001)
    for _ in range(3 * tracing.STALL_RECENT // tracing.STALL_FACTOR):
        with tracing.phase(tot, "read"):
            time.sleep(0.004)
    # k long reads among STALL_RECENT make the mean k / STALL_RECENT of one
    assert 1 <= tot.stall_counts["read"] <= (
        tracing.STALL_RECENT // tracing.STALL_FACTOR + 1)


def test_a_stall_nobody_sampled_is_filed_unsampled(monkeypatch):
    """It ended before the watchdog looked (here: before there was one)."""
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.01)
    tot = tracing.PhaseTotals("engine", ("read",), watch=("read",))
    _lately(tot, "read", 0.001)
    t_before = time.time()
    with tracing.phase(tot, "read"):
        time.sleep(0.03)
    filed = []
    watch = tracing.StallWatch(tot, dict, filed.append).start()
    watch.stop()
    assert not watch._thread.is_alive() and watch.samples == 0
    [record] = filed
    assert record["sampled"] is False and record["phase"] == "read"
    assert record["dur_s"] >= 0.03
    assert t_before - 1 <= record["t"] <= time.time()
    assert set(record) == {"t", "phase", "dur_s", "limit_s", "sampled"}
    assert record["limit_s"] == pytest.approx(0.01) and not tot.stalled


def test_what_the_kernel_does_not_show_is_left_out(monkeypatch):
    read = tracing._read
    # a sandbox's /proc (the chip's machines run under one) has a thread's
    # `stat` and little else
    hidden = ("/proc/pressure/", "/proc/self/io", "/wchan", "/schedstat",
              "/status")
    monkeypatch.setattr(tracing, "_read", lambda path: None if any(
        h in path for h in hidden) else read(path))
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.01)
    monkeypatch.setattr(tracing, "STALL_POLL_S", 0.005)
    tot = tracing.PhaseTotals("engine", ("read",), watch=("read",))
    _lately(tot, "read", 0.001)
    filed = []
    watch = tracing.StallWatch(tot, lambda: {"inflight": 1},
                               filed.append).start()
    try:
        with tracing.phase(tot, "read"):
            t0 = time.monotonic()
            while not watch.samples and time.monotonic() - t0 < WAIT_S:
                time.sleep(0.005)
    finally:
        watch.stop()
    [record] = filed
    assert record["sampled"] is True and record["engine"] == {"inflight": 1}
    assert "pressure" not in record and "proc_io" not in record
    assert record["threads"]
    for row in record["threads"]:
        assert {"tid", "name", "state", "cpu_s"} <= set(row) <= {
            "tid", "name", "state", "cpu_s", "py"}
    if os.path.exists("/proc/pressure/cpu"):      # where the kernel has them
        monkeypatch.setattr(tracing, "_read", read)
        both = tracing.StallWatch._differences(
            0.0, dict(tracing._process_sample(True), engine={}),
            tracing._process_sample(False))
        assert set(both["pressure"]["cpu"]) <= {"some", "full"}
        assert set(both["proc_io"]) == {"read_bytes", "write_bytes"}
        assert all({"runq_s", "vol", "invol"} <= set(r)
                   for r in both["threads"])


def _a_watch_looking_by_hand(monkeypatch, describe=dict):
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.01)
    tot = tracing.PhaseTotals("engine", ("read",), watch=("read",))
    _lately(tot, "read", 0.001)
    filed = []
    return tot, filed, tracing.StallWatch(tot, describe, filed.append)


def test_an_exit_between_the_two_reads_of_a_look_keeps_its_sample(
        monkeypatch):
    """The engine's thread appends the stalled exit and THEN clears `open`;
    a look that falls between the two still finds the entry open. The record
    on the queue says it has ended, so it is filed with both samples."""
    tot, filed, watch = _a_watch_looking_by_hand(monkeypatch)
    with tracing.phase(tot, "read"):
        entry = tot.open
        time.sleep(0.03)
        watch._look()                             # the first sample
        assert watch.samples == 1 and not filed
    tot.open = entry                              # as if not yet cleared
    watch._look()
    [record] = filed
    assert record["sampled"] is True and record["dur_s"] >= 0.03
    assert watch._first is None and watch.samples == 1
    tot.open = None
    watch._look()
    assert len(filed) == 1


def test_a_sample_that_fails_files_the_bare_record(monkeypatch):
    """A /proc line nobody foresaw, a `describe()` that raises: the stall is
    counted and filed all the same, and the watchdog goes on."""
    def describe():
        raise KeyError("gone")

    tot, filed, watch = _a_watch_looking_by_hand(monkeypatch, describe)
    watch.start()
    try:
        for _ in range(2):
            with tracing.phase(tot, "read"):
                t0 = time.monotonic()
                n = watch.samples
                while watch.samples == n and time.monotonic() - t0 < WAIT_S:
                    time.sleep(0.005)
            _lately(tot, "read", 0.001)
        assert watch._thread.is_alive()
    finally:
        watch.stop()
    assert tot.stall_counts["read"] == len(filed) == 2
    for record in filed:
        assert record["sampled"] is False and "gone" in record["sample_error"]
        assert "threads" not in record


def test_every_stalled_exit_is_filed_once_under_a_fast_watchdog(monkeypatch):
    """Two threads on `open` and `stalled`, the interpreter switching as
    often as it can: eight stalls among 2000 entries, each filed once."""
    monkeypatch.setattr(tracing, "STALL_FLOOR_S", 0.002)
    monkeypatch.setattr(tracing, "STALL_POLL_S", 0.0005)
    tot = tracing.PhaseTotals("engine", ("read",), watch=("read",))
    _lately(tot, "read", 1e-6)
    filed = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    watch = tracing.StallWatch(tot, dict, filed.append).start()
    try:
        for i in range(2000):
            with tracing.phase(tot, "read"):
                if i % 250 == 249:
                    time.sleep(0.004)
    finally:
        watch.stop()
        sys.setswitchinterval(interval)
    assert not watch._thread.is_alive()
    assert tot.stall_counts["read"] == len(filed) == 8
    assert len({r["t"] for r in filed}) == 8 and not tot.stalled
    assert sum(r["dur_s"] for r in filed) == pytest.approx(
        tot.stall_seconds["read"], abs=1e-4)


def test_watching_leaves_jax_out():
    """`StallWatch` samples from /proc and the interpreter: a process that
    must never load jax can watch its own loop."""
    code = (
        "import sys, time; import ray_tpu.util.tracing as t; "
        "t.STALL_FLOOR_S = 0.01; "
        "tot = t.PhaseTotals('loop', ['a'], watch=['a']); "
        "tot.recent['a'].extend([0.001] * 8); "
        "filed = []; w = t.StallWatch(tot, dict, filed.append).start(); "
        "exec('with t.phase(tot, \"a\"): time.sleep(0.3)'); w.stop(); "
        "assert len(filed) == 1 and tot.stall_counts['a'] == 1, filed; "
        "assert not hasattr(t, 'sample_rate'); "
        "assert not hasattr(t, 'set_process_label'); "
        "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], timeout=WAIT_S,
                          env=dict(os.environ)).returncode == 0
