"""ObjectRef — the distributed future (reference: python/ray/includes/object_ref.pxi).

Pickleable: serializes to its id; on deserialization it binds to the current
process's runtime client (driver or worker). Only the original driver-side ref
participates in refcounting (`_owned`); refs reconstructed in workers are
borrows, matching the reference's owner/borrower split
(src/ray/core_worker/reference_count.h) collapsed to the single-owner case.

Refs returned by `.remote()` carry CLIENT-derived ids
(ids.object_id_for_return) — submit is fire-and-forget and this ref exists
before the controller has seen the task. The incref/decref calls below are
coalesced by the client's delta flusher into batched frames; the flusher's
flush-before-anything-blocking rule keeps them ordered after the put/submit
that created the id, so a __del__-driven decref can never evict an object a
later-issued operation still expects (see client._DeltaFlusher).
"""

import asyncio
import collections
import functools

from . import protocol


class ObjectRef:
    __slots__ = ("id", "_owned", "__weakref__")

    def __init__(self, object_id: str, owned: bool = False):
        self.id = object_id
        self._owned = owned

    def __reduce__(self):
        # Simplified borrower protocol (ref:
        # src/ray/core_worker/reference_count.h): each DESERIALIZED copy
        # increfs once (in _rebuild_ref) and decrefs on GC — incref at pickle
        # time would unbalance whenever the bytes are deserialized 0 or >1
        # times. The sender-alive-until-rebuild gap is closed by containment
        # pinning: serialization records this id (note_contained_ref) and the
        # runtime pins it on behalf of the containing object/task until that
        # container is itself evicted/finished.
        from . import serialization
        serialization.note_contained_ref(self.id)
        return (_rebuild_ref, (self.id,))

    def hex(self) -> str:
        return self.id

    def __hash__(self):
        return hash(self.id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.id == self.id

    def __repr__(self):
        return f"ObjectRef({self.id})"

    def future(self):
        """A concurrent.futures.Future resolving to the object's value."""
        from . import state
        return state.global_client().as_future(self)

    def __await__(self):
        # usable in asyncio code (serve handles, async actors)
        import asyncio
        fut = self.future()
        return asyncio.wrap_future(fut).__await__()

    def __del__(self):
        if self._owned:
            try:
                from . import state
                client = state.global_client_or_none()
                if client is not None:
                    client.decref(self.id)
            except Exception:  # noqa: BLE001 - interpreter teardown
                pass


def _rebuild_ref(object_id: str):
    from . import state
    client = state.global_client_or_none()
    owned = False
    if client is not None:
        try:
            client.incref(object_id)
            owned = True  # this copy's GC decref balances the incref above
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass
    return ObjectRef(object_id, owned=owned)


class ObjectRefGenerator:
    """Streaming generator handle (ref: python/ray/_raylet.pyx
    ObjectRefGenerator). Iterates ObjectRefs for values yielded by a
    `num_returns="streaming"` task as they become available: one ObjectRef
    a `next()`, in yield order, each item once.

    A read of the stream hands over what is there: one controller call
    (client.read_stream) waits for the first item this reader has not seen
    and returns EVERY item the stream holds from there on, each with its
    descriptor. The batch waits in `_buffer` and `next()` is served from it,
    so a reader that keeps up makes one call an item and a reader that lags
    takes its backlog in one. The async form awaits the same call; no
    executor thread, buffered or not.

    Who releases what: every item a batch handed over carries the reference
    its put registered, and this reader owns it from then on. `next()` moves
    it into the ObjectRef it returns; `next_value()` (the serve handle's
    form: the value, no ObjectRef) keeps it until the next read, which
    carries the whole batch's references back in the same call; a generator
    dropped with values taken or items in its buffer gives theirs back from
    `__del__` in ONE flusher entry. Items no read reached stay the
    controller's (StreamState.max_served)."""

    def __init__(self, task_id: str):
        self.task_id = task_id
        self._index = 0  # items read from the controller so far
        self._buffer = collections.deque()  # (oid, descriptor) not yet handed out
        self._spent = []  # oids handed out as values: the next read gives them back
        self._inflight = None  # the one read under way (a concurrent Future)
        try:
            from . import state
            client = state.global_client_or_none()
            if client is not None:
                client.open_stream(task_id)
        except Exception:  # noqa: BLE001
            pass

    # -- the read ------------------------------------------------------------
    def _start_read(self, client):
        """The read under way, started if there is none. At most one at a
        time: a second from the same index would hand every item over twice."""
        if self._inflight is None:
            spent, self._spent = self._spent, []
            self._inflight = client.read_stream(
                self.task_id, self._index, release=spent)
        return self._inflight

    def _finish_read(self, fut) -> bool:
        """Take a read's batch into the buffer (the sync form waits for it
        here); False at the end of the stream. Raises what the read raised
        (the producer's error, after every item that came before it). An
        interrupt while waiting leaves the read under way for the next
        call."""
        try:
            batch = fut.result()
        finally:
            if fut.done() and self._inflight is fut:
                self._inflight = None
        if batch is None:
            return False
        protocol.note_stream_read(len(batch))
        self._index += len(batch)
        self._buffer.extend(batch)
        return True

    def _fill(self) -> bool:
        from . import state
        return self._finish_read(self._start_read(state.global_client()))

    async def _afill(self) -> bool:
        from . import state
        fut = self._start_read(state.global_client())
        if not fut.done():
            loop = asyncio.get_running_loop()
            landed = loop.create_future()

            def wake(_fut):
                try:
                    loop.call_soon_threadsafe(
                        lambda: landed.done() or landed.set_result(None))
                except RuntimeError:
                    pass  # the reader's loop closed under the read

            fut.add_done_callback(wake)
            # a cancel here cancels `landed` alone: the read stays under way
            # and its batch is taken by the next call, or released by __del__
            await landed
        return self._finish_read(fut)

    # -- one ObjectRef a call --------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self) -> ObjectRef:
        if not self._buffer and not self._fill():
            raise StopIteration
        return ObjectRef(self._buffer.popleft()[0], owned=True)

    def __aiter__(self):
        return self

    async def __anext__(self) -> ObjectRef:
        if not self._buffer and not await self._afill():
            raise StopAsyncIteration
        return ObjectRef(self._buffer.popleft()[0], owned=True)

    # -- one VALUE a call: no ObjectRef, no second call for a descriptor -----
    def _pop_value(self):
        from . import state
        oid, desc = self._buffer.popleft()
        self._spent.append(oid)
        return state.global_client()._materialize_one(oid, desc)

    def next_value(self):
        """The next item's value; StopIteration at the end."""
        if not self._buffer and not self._fill():
            raise StopIteration
        return self._pop_value()

    async def anext_value(self):
        """The next item's value; StopAsyncIteration at the end."""
        if not self._buffer and not await self._afill():
            raise StopAsyncIteration
        return self._pop_value()

    def __reduce__(self):
        # in-transit hold: the containing object/task keeps the stream open
        # until the receiver's own open_stream lands (prefix-dispatched like
        # nested ObjectRefs / actor handles)
        from . import serialization
        serialization.note_contained_ref(self.task_id)
        return (ObjectRefGenerator, (self.task_id,))

    def __del__(self):
        # abandoning a half-iterated stream releases its buffered state:
        # what a read handed this reader is the reader's to give back, the
        # rest goes with the controller's StreamState at close_stream
        try:
            from . import state
            client = state.global_client_or_none()
            if client is None:
                return
            client.release_stream_items(
                self._spent + [oid for oid, _ in self._buffer])
            if self._inflight is not None:
                # a read nobody will finish: release its batch when it lands
                self._inflight.add_done_callback(
                    functools.partial(_release_unread, client))
            client.close_stream(self.task_id)
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass


def _release_unread(client, fut):
    try:
        batch = fut.result()
    except BaseException:  # noqa: BLE001 - nothing was handed over
        return
    if batch:
        client.release_stream_items([oid for oid, _ in batch])


DynamicObjectRefGenerator = ObjectRefGenerator
