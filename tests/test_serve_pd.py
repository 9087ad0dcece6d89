"""Prefill/Decode disaggregation (VERDICT r4 missing #3; ref:
python/ray/llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py).

Greedy decoding is deterministic, so the strongest correctness check is
exact token equality: a PD pipeline (separate prefill + decode engines,
KV shipped between them) must produce byte-identical generations to one
colocated engine with the same weights."""

import asyncio

import numpy as np
import pytest


def _cfg(**kw):
    from ray_tpu.serve.llm import LLMConfig
    base = dict(preset="tiny", max_batch_slots=4, max_seq_len=128,
                paged=True, page_size=16, prefill_chunk=32,
                prefix_cache=False, seed=3)
    base.update(kw)
    return LLMConfig(**base)


@pytest.fixture(scope="module")
def servers():
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.pd import PDServer, PrefillServer
    plain = LLMServer(_cfg())
    prefill = PrefillServer(_cfg())
    pd = PDServer(_cfg(), prefill=prefill)
    return plain, prefill, pd


def test_pd_matches_colocated_greedy(servers):
    plain, _, pd = servers
    prompts = [list(range(5, 25)), [7, 3, 11] * 9, list(range(60, 100))]

    async def gen(server, p):
        return await server.generate(p, max_tokens=12)

    for p in prompts:
        ref = asyncio.run(gen(plain, p))
        got = asyncio.run(gen(pd, p))
        assert got["tokens"] == ref["tokens"], (p[:4], got, ref)
    assert pd.pd_requests == len(prompts)
    assert pd.stats()["pd_requests"] == len(prompts)


def test_pd_concurrent_requests(servers):
    plain, _, pd = servers

    async def many(server):
        outs = await asyncio.gather(*[
            server.generate([i + 2, i + 5, i + 9], max_tokens=8)
            for i in range(6)])
        return [o["tokens"] for o in outs]

    assert asyncio.run(many(pd)) == asyncio.run(many(plain))
    # all slots/pages returned on both engines
    for s in (plain, pd):
        st = s.stats()
        assert st["active"] == 0 and st["free_slots"] == 4
        assert st["pages_in_use"] == 0


def test_pd_logprobs_and_eos(servers):
    plain, _, pd = servers
    p = list(range(30, 50))

    async def gen(server):
        return await server.generate(p, max_tokens=6, logprobs=True)

    ref = asyncio.run(gen(plain))
    got = asyncio.run(gen(pd))
    assert got["tokens"] == ref["tokens"]
    np.testing.assert_allclose(got["logprobs"], ref["logprobs"],
                               rtol=1e-4, atol=1e-5)

    # eos on the FIRST (prefill-produced) token truncates to empty
    eos = ref["tokens"][0]
    got_eos = asyncio.run(pd.generate(p, max_tokens=6, eos_id=eos))
    assert got_eos["tokens"] == []


@pytest.mark.parametrize("case", ["late_join", "max_tokens_1",
                                  "max_tokens_2", "walks_away"])
def test_pd_first_token_joins_with_a_host_value(servers, case):
    """The decode replica knows a request's first token on the host (the
    prefill replica sampled it), so its slot joins the device-resident slot
    state through the SAME join as a colocated first token, with a host
    value: it decodes from the next chunk dispatched while another request's
    chunk is in flight, and ends where the colocated engine ends it."""
    plain, _, pd = servers
    long_p, late_p = list(range(40, 70)), [9, 4, 17] * 7
    n_late = {"max_tokens_1": 1, "max_tokens_2": 2}.get(case, 9)
    before = pd.stats()["decode"]

    async def late(server):
        if case != "walks_away":
            return (await server.generate(late_p, max_tokens=n_late))["tokens"]
        got = []
        async for tok in server.generate_stream(late_p, max_tokens=40):
            got.append(tok)
            if len(got) == 4:
                break
        return got

    async def both(server):
        first = asyncio.ensure_future(server.generate(long_p, max_tokens=20))
        while not server._inflight:
            await asyncio.sleep(0)       # the long request decodes
        out = await late(server)
        return (await first)["tokens"], out

    async def ref():
        return ((await plain.generate(long_p, max_tokens=20))["tokens"],
                (await plain.generate(late_p, max_tokens=40))["tokens"])

    want_long, want_late = asyncio.run(ref())
    got_long, got_late = asyncio.run(both(pd))
    assert got_long == want_long
    assert got_late == want_late[:len(got_late)]
    assert len(got_late) == (4 if case == "walks_away" else n_late)
    d = pd.stats()["decode"]
    assert d["joined_on_device"] == before["joined_on_device"]  # host values
    if case == "late_join":
        assert d["run_ahead_chunks"] > before["run_ahead_chunks"]
    st = pd.stats()
    assert st["active"] == 0 and st["free_slots"] == 4
    assert st["pages_in_use"] == 0
    assert not np.asarray(pd._slots.active).any()
    assert not pd._inflight


def test_pd_requires_paged():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.pd import PrefillServer
    with pytest.raises(ValueError, match="paged"):
        server = PrefillServer(LLMConfig(preset="tiny", paged=False,
                                         max_seq_len=64))
        asyncio.run(server.prefill_begin([1, 2, 3]))


# ------------------- streaming data plane (zero-copy KV-page shipment) ---

def _no_arrays(x, where=""):
    """Control frames must carry metadata only — any ndarray in a header
    or segment dict means KV bytes went back into the RPC plane."""
    if isinstance(x, np.ndarray):
        raise AssertionError(f"ndarray leaked into control frame at {where}")
    if isinstance(x, dict):
        for k, v in x.items():
            _no_arrays(v, f"{where}.{k}")
    elif isinstance(x, (list, tuple)):
        for i, v in enumerate(x):
            _no_arrays(v, f"{where}[{i}]")


def test_stream_frames_carry_no_kv_bytes(servers):
    _, prefill, _ = servers

    async def drive():
        header = await prefill.prefill_begin(list(range(2, 39)))
        _no_arrays(header, "header")
        have, done = 0, False
        while not done:
            res = await prefill.prefill_wait(header["ship_id"], have)
            _no_arrays(res, "wait")
            have += len(res["segments"])
            done = res["done"]
        assert have >= 1
        assert isinstance(res["token"], int)     # the first token, sampled
        await prefill.prefill_drop(header["ship_id"])
        return header

    header = asyncio.run(drive())
    assert header["total_pages"] == 3 and header["prompt_len"] == 37
    # the prefill slot was released (nothing leaks); drop freed every segment
    assert prefill.stats()["active"] == 0
    assert prefill.stats()["free_slots"] == 4


def test_stream_suffix_install_parity():
    """Prefix-cache on both sides: the second request sharing 2 leading
    pages must ship only its suffix AND still decode bit-identically."""
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.pd import PDServer, PrefillServer
    from ray_tpu.util import metrics as _metrics

    ref = LLMServer(_cfg(prefix_cache=True))
    prefill = PrefillServer(_cfg(prefix_cache=True), params=ref.params)
    pd = PDServer(_cfg(prefix_cache=True), params=ref.params,
                  prefill=prefill)

    p1 = list(range(5, 42))               # 37 tokens -> 3 pages
    p2 = p1[:32] + [91, 92, 93, 94, 95]   # shares the first 2 pages

    async def both(server):
        a = await server.generate(p1, max_tokens=8)
        b = await server.generate(p2, max_tokens=8)
        return a["tokens"], b["tokens"]

    before = _metrics.kv_ship_counters()
    got = asyncio.run(both(pd))
    want = asyncio.run(both(ref))
    assert got == want
    after = _metrics.kv_ship_counters()
    # the shared prefix pages were never shipped for p2
    assert after["saved_pages"] - before["saved_pages"] >= 2
    assert after["pages"] - before["pages"] <= 4  # 3 (p1) + 1 suffix (p2)


def test_stream_forced_remote_pull(servers, monkeypatch):
    """A reader that cannot attach the writer's segment by name (as on
    another host) takes the next rung: the KVDataServer + parallel_fetch
    ranged-transfer path."""
    from ray_tpu.serve.kv_transfer import ShipReader
    from ray_tpu.util import metrics as _metrics
    plain, _, pd = servers
    attach = ShipReader._attach
    # the local copy parallel_fetch lands (`delete`) is still attached
    monkeypatch.setattr(
        ShipReader, "_attach",
        lambda self, oid, seg, layout, delete: (
            attach(self, oid, seg, layout, delete) if delete else None))
    p = list(range(11, 53))
    before = _metrics.kv_ship_counters()
    got = asyncio.run(pd.generate(p, max_tokens=10))
    ref = asyncio.run(plain.generate(p, max_tokens=10))
    assert got["tokens"] == ref["tokens"]
    after = _metrics.kv_ship_counters()
    assert after["stream_pulls"] - before["stream_pulls"] >= 1
    assert after["attach_hits"] == before["attach_hits"]


def test_serving_bench_smoke_gate():
    """Tier-1 hook for the serving bench's --smoke mode: a subprocess PD
    round trip on CPU must ship KV through the streaming plane (counters
    nonzero) with zero KV bytes in the RPC control frames."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks",
                                      "serving_bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert p.returncode == 0, (p.stdout, p.stderr)
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["smoke"] == "ok"
    assert rec["kv_ship"]["bytes"] > 0 and rec["kv_ship"]["pages"] > 0
    assert rec["kv_ship"]["rpc_fallback_bytes"] == 0


def test_pd_slo_histograms_tagged(servers):
    """PD requests must land in the serving SLO histograms under path=pd
    (the colocated path records path=local) — satellite of the streaming
    rework: TTFT/TPOT were previously never observed for PD."""
    from ray_tpu.util import metrics as _metrics
    _, _, pd = servers
    asyncio.run(pd.generate(list(range(40, 70)), max_tokens=8))

    def series_tags(name):
        m = _metrics._registry.get(name)
        assert m is not None, f"{name} not registered"
        return [dict(k) for k in m.snapshot()["count"]]

    assert any(t.get("path") == "pd" for t in series_tags("serve_ttft_s"))
    assert any(t.get("path") == "pd" for t in series_tags("serve_tpot_ms"))
