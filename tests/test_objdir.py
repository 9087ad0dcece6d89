"""Native/Python object-directory equivalence tests (ISSUE 14 tentpole 2 +
satellite c).

PyObjectDirectory is the executable spec: randomized op sequences —
register / holder churn / refcount deltas / evict(erase) / node death — must
drive the C++ ObjectDirectory to byte-identical snapshot() state and
identical apply_deltas() verdicts at every checkpoint. The native side skips
cleanly on a toolchain-less box (conftest's report header says so); the
Python side always runs, so the fallback path is tested everywhere.
"""

import random

import pytest

from ray_tpu._native import objdir
from ray_tpu._native.objdir import (DECREF, F_EVICTABLE, F_RELEASED, INCREF,
                                    PyObjectDirectory)

NSHARDS = 8

_LOCATIONS = ["pending", "shm", "inline", "spilled", "error",
              "remote:node-2", "plasma://custom"]


def _pair():
    """(native, oracle) — or skip when the toolchain can't build the .so."""
    if not objdir.available():
        pytest.skip("no toolchain: native obj_directory unavailable")
    return objdir.ObjectDirectory(NSHARDS), PyObjectDirectory(NSHARDS)


def _both(fn):
    nat, py = _pair()
    try:
        assert fn(nat) == fn(py)
    finally:
        nat.close()


# ------------------------------------------------------------- scalar ops

def test_register_get_set_roundtrip():
    def run(d):
        d.register("obj-a", refcount=2, pinned=1, size=100, location="shm")
        out = [d.contains("obj-a"), d.contains("obj-b"), d.count(),
               d.refcount("obj-a"), d.pinned("obj-a"), d.size("obj-a"),
               d.location("obj-a")]
        d.set_refcount("obj-a", 5)
        d.set_pinned("obj-a", 0)
        d.set_size("obj-a", 4096)
        d.set_location("obj-a", "remote:node-9")
        out += [d.refcount("obj-a"), d.pinned("obj-a"), d.size("obj-a"),
                d.location("obj-a"), d.total_bytes()]
        # missing ids answer None/False everywhere, never raise
        out += [d.refcount("obj-nope"), d.pinned("obj-nope"),
                d.size("obj-nope"), d.location("obj-nope"),
                d.add_refcount("obj-nope", 1), d.erase("obj-nope")]
        out += [d.add_refcount("obj-a", -2), d.erase("obj-a"), d.count()]
        return out
    _both(run)


def test_location_codes_roundtrip():
    def run(d):
        for i, loc in enumerate(_LOCATIONS):
            d.register(f"obj-{i}", location=loc)
        return [d.location(f"obj-{i}") for i in range(len(_LOCATIONS))]
    _both(run)


def test_holder_ops():
    def run(d):
        d.register("obj-a")
        out = [d.add_holder("obj-a", "node-1"),      # True
               d.add_holder("obj-a", "node-1"),      # dup -> False
               d.add_holder("obj-a", "node-2"),
               d.add_holder("obj-missing", "node-1"),  # no entry -> False
               sorted(d.holders("obj-a")),
               d.remove_holder("obj-a", "node-1"),
               d.remove_holder("obj-a", "node-1"),   # gone -> False
               d.holders("obj-a"), d.holders("obj-missing")]
        d.add_holder("obj-a", "node-3")
        d.clear_holders("obj-a")
        out.append(d.holders("obj-a"))
        return out
    _both(run)


def test_drop_node_touch_count():
    def run(d):
        for i in range(6):
            d.register(f"obj-{i}")
            d.add_holder(f"obj-{i}", "node-dead" if i % 2 else "node-ok")
        touched = d.drop_node("node-dead")
        return [touched, [d.holders(f"obj-{i}") for i in range(6)]]
    _both(run)


# ------------------------------------------------------------- delta runs

def test_apply_deltas_flags():
    def run(d):
        d.register("obj-a", refcount=1, pinned=0)   # -> released + evictable
        d.register("obj-b", refcount=2, pinned=1)   # -> released, pinned
        d.register("obj-c", refcount=1)             # inc then dec: net zero
        packed = objdir.pack_deltas([
            (DECREF, "obj-a"),
            (DECREF, "obj-b"), (DECREF, "obj-b"),
            (INCREF, "obj-c"), (DECREF, "obj-c"),
            (DECREF, "obj-ghost"),                  # unknown id: ignored
        ])
        return d.apply_deltas(packed)
    nat, py = _pair()
    try:
        res_nat, res_py = run(nat), run(py)
        assert res_nat == res_py
        by_id = dict((oid, (flags, rc)) for oid, flags, rc in res_py)
        assert by_id["obj-a"] == (F_RELEASED | F_EVICTABLE, 0)
        assert by_id["obj-b"] == (F_RELEASED, 0)       # pinned blocks evict
        assert by_id["obj-c"] == (0, 1)                # never crossed zero
        assert "obj-ghost" not in by_id
    finally:
        nat.close()


def test_apply_deltas_released_once():
    # F_RELEASED fires on the FIRST crossing to <= 0 only; later oscillation
    # around zero reports rc but not the flag again
    def run(d):
        d.register("obj-a", refcount=1)
        first = d.apply_deltas(objdir.pack_deltas([(DECREF, "obj-a")]))
        second = d.apply_deltas(objdir.pack_deltas(
            [(INCREF, "obj-a"), (DECREF, "obj-a")]))
        return [first, second]
    nat, py = _pair()
    try:
        out = run(py)
        assert run(nat) == out
        assert out[0] == [("obj-a", F_RELEASED | F_EVICTABLE, 0)]
        assert out[1] == [("obj-a", F_EVICTABLE, 0)]
    finally:
        nat.close()


def test_apply_deltas_empty_and_malformed():
    nat, py = _pair()
    try:
        assert nat.apply_deltas(b"") == py.apply_deltas(b"") == []
        for bad in (b"\x01", b"\x01\x05\x00ob", b"\x07\x03\x00abc"):
            with pytest.raises(ValueError):
                py.apply_deltas(bad)
            with pytest.raises(ValueError):
                nat.apply_deltas(bad)
    finally:
        nat.close()


def test_pack_unpack_delta_layouts():
    packed = objdir.pack_deltas([(INCREF, "obj-a"), (DECREF, "obj-bb")])
    assert packed == b"\x01\x05\x00obj-a\x02\x06\x00obj-bb"
    # output layout: u8 flags | i64 rc LE | u16 idlen | id
    blob = (b"\x03" + (0).to_bytes(8, "little", signed=True)
            + b"\x05\x00obj-a")
    assert objdir.unpack_delta_result(blob) == [("obj-a", 3, 0)]


# --------------------------------------------------- randomized equivalence

def _random_op(rng, nat, py, ids, nodes):
    """Apply one random mutation to BOTH directories; return any comparable
    result pair (they must match)."""
    oid = rng.choice(ids)
    roll = rng.random()
    if roll < 0.25:
        rc = rng.randint(-1, 4)
        pin = rng.randint(0, 2)
        size = rng.randint(0, 1 << 20)
        loc = rng.choice(_LOCATIONS)
        nat.register(oid, rc, pin, size, loc)
        py.register(oid, rc, pin, size, loc)
        return None
    if roll < 0.40:  # packed delta run over several ids
        run = [(rng.choice((INCREF, DECREF)), rng.choice(ids))
               for _ in range(rng.randint(1, 8))]
        packed = objdir.pack_deltas(run)
        return nat.apply_deltas(packed), py.apply_deltas(packed)
    if roll < 0.50:
        delta = rng.choice((-2, -1, 1, 2))
        return nat.add_refcount(oid, delta), py.add_refcount(oid, delta)
    if roll < 0.60:
        node = rng.choice(nodes)
        return nat.add_holder(oid, node), py.add_holder(oid, node)
    if roll < 0.68:
        node = rng.choice(nodes)
        return nat.remove_holder(oid, node), py.remove_holder(oid, node)
    if roll < 0.74:  # evict
        return nat.erase(oid), py.erase(oid)
    if roll < 0.80:
        pin = rng.randint(0, 2)
        nat.set_pinned(oid, pin)
        py.set_pinned(oid, pin)
        return None
    if roll < 0.86:
        size = rng.randint(0, 1 << 16)
        nat.set_size(oid, size)
        py.set_size(oid, size)
        return None
    if roll < 0.92:
        loc = rng.choice(_LOCATIONS)
        nat.set_location(oid, loc)
        py.set_location(oid, loc)
        return None
    if roll < 0.97:
        v = rng.randint(-1, 5)
        nat.set_refcount(oid, v)
        py.set_refcount(oid, v)
        return None
    node = rng.choice(nodes)  # node death
    return nat.drop_node(node), py.drop_node(node)


@pytest.mark.parametrize("seed", [0, 1, 2, 1337])
def test_randomized_equivalence(seed):
    nat, py = _pair()
    rng = random.Random(seed)
    ids = [f"obj-{i}" for i in range(40)]
    nodes = [f"node-{i}" for i in range(5)]
    try:
        for step in range(600):
            pair = _random_op(rng, nat, py, ids, nodes)
            if pair is not None:
                assert pair[0] == pair[1], f"seed={seed} step={step}"
            if step % 100 == 99:
                assert nat.snapshot() == py.snapshot(), \
                    f"state diverged: seed={seed} step={step}"
        assert nat.snapshot() == py.snapshot()
        assert nat.count() == py.count()
        assert nat.total_bytes() == py.total_bytes()
        assert [nat.shard_count(i) for i in range(NSHARDS)] \
            == [py.shard_count(i) for i in range(NSHARDS)]
    finally:
        nat.close()


def test_sharding_spreads_ids():
    # fnv1a over a few hundred ids should touch most of the shards — the
    # whole point of the per-shard locks
    d = PyObjectDirectory(16)
    for i in range(400):
        d.register(f"obj-{i:04d}")
    occupied = sum(1 for i in range(16) if d.shard_count(i) > 0)
    assert occupied >= 12


# ----------------------------------------------------------- factory paths

def test_make_directory_escape_hatch(monkeypatch):
    monkeypatch.setenv("RAY_TPU_NATIVE", "0")
    assert isinstance(objdir.make_object_directory(), PyObjectDirectory)


def test_make_directory_native_when_available(monkeypatch):
    monkeypatch.delenv("RAY_TPU_NATIVE", raising=False)
    d = objdir.make_object_directory()
    try:
        if objdir.available():
            assert isinstance(d, objdir.ObjectDirectory)
            assert d.nshards == objdir.NUM_SHARDS
        else:
            assert isinstance(d, PyObjectDirectory)
    finally:
        d.close()


def test_directory_singleton_reset(monkeypatch):
    # put back afterwards what this process had: a session that an earlier
    # test file started on this xdist worker keeps resolving its object
    # metadata through the singleton, and must not be cut off from it
    monkeypatch.setattr(objdir, "_dir", objdir._dir)
    objdir.reset_directory()
    d1 = objdir.get_directory()
    assert objdir.get_directory() is d1
    objdir.reset_directory()
    d2 = objdir.get_directory()
    assert d2 is not d1
    objdir.reset_directory()
