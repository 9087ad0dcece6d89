"""A stream's reader takes everything its stream holds in one call.

`controller.read_stream` waits for the first item past the reader's index and
hands over every item the stream holds from there on, descriptors included;
`ObjectRefGenerator` serves `next()` / `next_value()` from that batch and the
serve handle's response yields the values. What must not change: order, each
item once, an error after the items before it, the end, the timeout. What
must: one blocking round trip an item where there were two, and a backlog
taken in far fewer reads than items.

Every case runs twice: with the reader in the driver (DriverClient: a bridge
call into the controller's loop) and inside an actor (WorkerClient: the
`next_stream` RPC). The scenarios are closures, so cloudpickle ships them to
the actor by value.
"""

import asyncio
import gc
import sys
import threading
import time

import cloudpickle
import pytest

# the actor's process cannot import this file: ship its helpers by value too
cloudpickle.register_pickle_by_value(sys.modules[__name__])


def _wait_for(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


@pytest.fixture(scope="module")
def producer(ray_session):
    ray = ray_session

    @ray.remote(num_cpus=0, max_concurrency=64)
    class Producer:
        async def count(self, n, tick_s=0.0, fail_at=None, big=False):
            import numpy as np
            for i in range(n):
                if i == fail_at:
                    raise ValueError("boom")
                if tick_s:
                    await asyncio.sleep(tick_s)
                # big: over the inline threshold, so the descriptor is ("shm", ..)
                yield np.full(20_000, i, np.float64) if big else i

    p = Producer.remote()
    assert list(_values(p.count.options(num_returns="streaming").remote(2))) \
        == [0, 1]
    yield p
    ray.kill(p)


@pytest.fixture(scope="module")
def reader_actor(ray_session):
    ray = ray_session

    @ray.remote(num_cpus=0)
    class Reader:
        def call(self, fn, *args):
            return fn(*args)

    r = Reader.remote()
    yield r
    ray.kill(r)


@pytest.fixture(params=["driver", "actor"])
def run(request, ray_session, reader_actor):
    """run(fn, *args): call a scenario where this case's reader lives."""
    if request.param == "driver":
        return lambda fn, *args: fn(*args)
    return lambda fn, *args: ray_session.get(
        reader_actor.call.remote(fn, *args), timeout=120)


def _stream(producer, *args, **kwargs):
    return producer.count.options(num_returns="streaming").remote(
        *args, **kwargs)


def _values(gen):
    while True:
        try:
            yield gen.next_value()
        except StopIteration:
            return


def test_order_and_exactly_once_with_32_reader_threads(run, producer):
    def scenario(producer):
        import ray_tpu
        n, out, errors = 200, {}, []

        def read(k):
            try:
                gen = _stream(producer, n)
                if k % 2:
                    out[k] = list(_values(gen))
                else:  # the contract of next(): one ObjectRef a call
                    out[k] = [ray_tpu.get(ref) for ref in gen]
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(repr(e))

        threads = [threading.Thread(target=read, args=(k,)) for k in range(32)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)  # more threads than cores, switched often
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(interval)
        alive = sum(t.is_alive() for t in threads)
        big = [int(a[0]) for a in _values(_stream(producer, 6, big=True))]
        return alive, errors, [out.get(k) == list(range(n)) for k in range(32)], big

    alive, errors, same, big = run(scenario, producer)
    assert alive == 0 and not errors, (alive, errors)
    assert all(same), same
    assert big == list(range(6))


def test_error_mid_stream_arrives_after_the_items_before_it(run, producer):
    def scenario(producer):
        import ray_tpu
        seen = {}
        for form in ("values", "refs"):
            gen = _stream(producer, 10, fail_at=5)
            time.sleep(0.5)  # the five items AND the error are there already
            got, err = [], None
            try:
                if form == "values":
                    for v in _values(gen):
                        got.append(v)
                else:
                    for ref in gen:
                        got.append(ray_tpu.get(ref))
            except Exception as e:  # noqa: BLE001 - the producer's error
                err = type(e).__name__ + ": " + str(e)
            seen[form] = (got, err)
        return seen

    for form, (got, err) in run(scenario, producer).items():
        assert got == [0, 1, 2, 3, 4], (form, got)
        assert err is not None and "boom" in err, (form, err)


def test_timeout_still_raises_and_loses_nothing(run, producer):
    def scenario(producer):
        from ray_tpu import exceptions
        from ray_tpu._private import state
        gen = _stream(producer, 2, tick_s=2.0)  # room for a loaded machine
        first = gen.next_value()
        t0 = time.monotonic()
        try:
            state.global_client().read_stream(gen.task_id, 1, 0.1).result(30)
            timed_out = False
        except exceptions.GetTimeoutError:
            timed_out = True
        waited = time.monotonic() - t0
        return first, timed_out, waited < 1.8, list(_values(gen))

    assert run(scenario, producer) == (0, True, True, [1])


def test_dropped_generator_leaves_no_object_behind(run, producer, ray_session):
    from ray_tpu._private import state
    ctl = state.global_client().controller

    def scenario(producer):
        import ray_tpu
        gen = _stream(producer, 50)
        tid = gen.task_id
        time.sleep(0.5)  # all fifty arrive; the first read takes them all
        ref = next(gen)  # one leaves in an ObjectRef
        taken = [ray_tpu.get(ref), gen.next_value(), gen.next_value()]
        buffered = len(gen._buffer)
        del ref, gen  # 2 values taken, 47 items in the buffer, none read again
        gc.collect()
        return tid, taken, buffered

    tid, _, _ = run(scenario, producer)  # what a first run leaves for good
    assert _wait_for(lambda: tid not in ctl.streams)
    # (other cases' generators may still wait for a collection in the actor's
    # process: when they go, the set only shrinks)
    before = set(ctl.objects)
    tid, taken, buffered = run(scenario, producer)
    assert taken == [0, 1, 2] and buffered == 47
    assert _wait_for(lambda: tid not in ctl.streams)
    assert _wait_for(lambda: set(ctl.objects) <= before), \
        sorted(set(ctl.objects) - before)[:5]


def test_an_errored_stream_leaves_no_state_behind(ray_session, producer):
    """The error a reader raises is a copy: the stored one must not grow a
    traceback through the driver-side reader's frames, or the generator those
    frames hold keeps its own StreamState (and the error) alive for good."""
    from ray_tpu import exceptions
    from ray_tpu._private import state
    ctl = state.global_client().controller
    gen = _stream(producer, 4, fail_at=2)
    tid = gen.task_id
    with pytest.raises(exceptions.TaskError):
        list(_values(gen))
    del gen
    gc.collect()
    assert _wait_for(lambda: tid not in ctl.streams)


@pytest.fixture(scope="module")
def squares(ray_session):
    from ray_tpu import serve

    @serve.deployment
    class Squares:
        async def stream(self, n):
            for i in range(n):
                yield i * i

    handle = serve.run(Squares.bind(), name="stream_batches")
    yield handle.options(method_name="stream", stream=True)
    serve.shutdown()


def test_async_forms_give_the_same_values(run, producer, squares):
    def scenario(producer, squares):
        async def main():
            refs = [await ref async for ref in _stream(producer, 40)]
            slow = []
            async for ref in _stream(producer, 8, tick_s=0.02):
                slow.append(await ref)
            served = [v async for v in squares.remote(30)]
            # a reader cancelled inside a read loses nothing: the read stays
            # under way and the next call takes its batch
            gen = _stream(producer, 3, tick_s=0.3)
            try:
                await asyncio.wait_for(gen.anext_value(), 0.05)
                cancelled = False
            except asyncio.TimeoutError:
                cancelled = True
            rest = [await gen.anext_value() for _ in range(3)]
            return refs, slow, served, cancelled, rest

        return asyncio.run(main())

    refs, slow, served, cancelled, rest = run(scenario, producer, squares)
    assert refs == list(range(40))
    assert slow == list(range(8))
    assert served == [i * i for i in range(30)]
    assert list(squares.remote(5)) == [0, 1, 4, 9, 16]
    assert cancelled and rest == [0, 1, 2]


def test_a_backlog_is_taken_in_far_fewer_reads_than_items(run, producer):
    def scenario(producer):
        from ray_tpu._private import protocol
        gen = _stream(producer, 1000)
        time.sleep(2.0)  # the reader sleeps while the items arrive
        c0 = dict(protocol.counter_snapshot()["streams"])
        got = list(_values(gen))
        c1 = protocol.counter_snapshot()["streams"]
        return got == list(range(1000)), c1["items"] - c0.get("items", 0), \
            c1["reads"] - c0.get("reads", 0)

    same, items, reads = run(scenario, producer)
    assert same
    assert items == 1000
    assert 1 <= reads and items / reads > 4, (items, reads)


def test_a_reader_that_keeps_up_makes_one_round_trip_an_item(run, producer):
    def scenario(producer):
        from ray_tpu._private import protocol
        n = 30
        gen = _stream(producer, n, tick_s=0.03)
        first = gen.next_value()  # the worker is up and the stream flows
        r0 = sum(protocol.counter_snapshot()["roundtrips"].values())
        c0 = dict(protocol.counter_snapshot()["streams"])
        rest = list(_values(gen))
        trips = sum(protocol.counter_snapshot()["roundtrips"].values()) - r0
        c1 = protocol.counter_snapshot()["streams"]
        return [first] + rest == list(range(n)), trips, \
            c1["items"] - c0["items"], c1["reads"] - c0["reads"]

    same, trips, items, reads = run(scenario, producer)
    assert same and items == 29
    # one read an item (a late wake-up may take two items in one), one more
    # to find the end; the parent made two round trips an item: 58
    assert reads <= items
    assert reads <= trips <= items + 3, (trips, items, reads)


def test_the_counters_only_grow(ray_session, producer):
    from ray_tpu.util import metrics
    seen = []
    for n in (3, 0, 7):
        assert list(_values(_stream(producer, n))) == list(range(n))
        seen.append(dict(metrics.control_plane_counters()["streams"]))
    for a, b in zip(seen, seen[1:]):
        assert b["reads"] >= a["reads"] and b["items"] >= a["items"]
    assert seen[-1]["items"] - seen[0]["items"] == 7
