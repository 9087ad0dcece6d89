"""Worker process entrypoint (reference: python/ray/_private/workers/default_worker.py).

Execution model: the controller dispatches up to `max_concurrency` exec
messages at once; a small thread pool runs them. Async actor methods run on a
persistent asyncio loop so `await` concurrency works like the reference's
async actors (python/ray/_private/async_compat.py). jax is never imported
here — tasks that need it import it themselves, keeping worker cold-start
~100ms.
"""

import asyncio
import inspect
import os
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import cloudpickle

from .. import exceptions as exc
from ..util import tracing
from . import ids, serialization, state
from .client import WorkerClient


_ActorExit = exc._ActorExit


class WorkerState:
    def __init__(self, client):
        self.client = client
        self.actor_instance = None
        self.actor_id = None
        self.fn_cache = {}
        self.async_loop = None
        self._loop_lock = threading.Lock()
        self.current = threading.local()

    def get_async_loop(self):
        # Double-checked under a lock: the first two calls of an async actor
        # routinely arrive on two pool threads at once (e.g. two collective
        # ranks hitting a rendezvous actor). An unguarded check-then-create
        # spawned TWO event loops, splitting the actor's coroutines across
        # loops — asyncio.Event.set() on one loop never wakes a waiter on
        # the other, which surfaced as the host-collective deadlock (r1).
        if self.async_loop is None:
            with self._loop_lock:
                if self.async_loop is None:
                    loop = asyncio.new_event_loop()
                    t = threading.Thread(target=loop.run_forever, daemon=True)
                    t.start()
                    self.async_loop = loop
        return self.async_loop


def current_worker():
    return state.worker_state()


def _load_fn(ws, blob):
    key = hash(blob)
    fn = ws.fn_cache.get(key)
    if fn is None:
        fn = cloudpickle.loads(blob)
        ws.fn_cache[key] = fn
    return fn


def _resolve_args(ws, spec, arg_descs=None):
    """Fetch top-level ObjectRef args (values inline; nested refs stay refs).

    `arg_descs` (dependency-prefetching dispatch) carries descriptors for
    args already resident in the shared local store: those materialize
    zero-copy here instead of through a blocking round trip. A descriptor
    that fails to materialize (segment vanished under us — holder death or
    eviction mid-prefetch) falls back to one blocking get with the rest, so
    a stale descriptor can never fail the task."""
    ref_oids = [v for k, v in list(spec.args) + list(spec.kwargs.values()) if k == "ref"]
    fetched = {}
    missing = []
    for oid in dict.fromkeys(ref_oids):
        d = (arg_descs or {}).get(oid)
        if d is None:
            missing.append(oid)
            continue
        try:
            kind, payload = d
            if kind == "inline":
                fetched[oid] = serialization.unpack(payload)
            else:  # ("shm", meta_len): zero-copy from the shared store
                fetched[oid] = ws.client.store.get(oid, payload)
        except Exception:  # noqa: BLE001 - stale descriptor → exec-time fetch
            missing.append(oid)
    if missing:
        values = ws.client.get(missing)
        fetched.update(zip(missing, values))
    args = [fetched[v] if k == "ref" else serialization.unpack(v) for k, v in spec.args]
    kwargs = {name: (fetched[v] if k == "ref" else serialization.unpack(v))
              for name, (k, v) in spec.kwargs.items()}
    return args, kwargs


def _warm_next(ws):
    """Lookahead resolution: while the pool computes task N, touch the shm
    segments of queued task N+1 so its _resolve_args is a warm zero-copy
    attach (the dispatch loop is otherwise idle between exec frames).
    Purely advisory — a vanished segment is task N+1's fallback problem."""
    try:
        with ws.client.task_available:
            nxt = (ws.client.task_queue[0]
                   if ws.client.task_queue else None)
        if not nxt:
            return
        t0 = time.time()
        warmed = 0
        for oid, d in (nxt.get("arg_descs") or {}).items():
            if d and d[0] == "shm":
                ws.client.store.warm(oid, d[1])
                warmed += 1
        if warmed and tracing.enabled():
            nspec = nxt.get("spec")
            tracing.record_span(
                "worker.warm_next", "worker",
                getattr(nspec, "trace_id", None), tracing.new_span_id(),
                None, t0, time.time() - t0, args={"args_warmed": warmed})
    except Exception:  # noqa: BLE001 - warming must never hurt dispatch
        pass


def _call(ws, fn, args, kwargs):
    if inspect.iscoroutinefunction(fn):
        import concurrent.futures
        loop = ws.get_async_loop()
        fut = asyncio.run_coroutine_threadsafe(fn(*args, **kwargs), loop)
        try:
            # Wait in short slices: a targeted cancel (ray_tpu.cancel →
            # cancel_exec) raises KeyboardInterrupt in THIS thread via
            # PyThreadState_SetAsyncExc, which only fires while bytecode
            # runs — an indefinite C-level result() wait would never see it.
            # concurrent.futures.wait (NOT result(timeout=...)): on 3.11+
            # futures.TimeoutError IS builtin TimeoutError, so catching it
            # around result() would swallow a coroutine's own TimeoutError
            # and spin forever.
            while True:
                done, _ = concurrent.futures.wait([fut], timeout=0.1)
                if done:
                    return fut.result()
        except KeyboardInterrupt:
            # propagate into the coroutine so the replica's in-flight slot
            # frees (asyncio.CancelledError inside the task)
            fut.cancel()
            raise
    return fn(*args, **kwargs)


def _execute(ws, p):
    spec = p["spec"]
    result_oids = p["result_oids"]
    ws.client.current_task_id = spec.task_id
    ws.current.spec = spec
    # thread-local trace context: nested submits from this task inherit
    # the trace, log records pick up trace_id, and the stamps below let
    # the controller split exec from publish in the task's phase spans
    traced = spec.trace_id is not None and tracing.enabled()
    if traced:
        tracing.set_current(spec.trace_id, spec.parent_span_id)
    t_res0 = t_exec0 = time.time()
    error = None
    results = []
    try:
        args, kwargs = _resolve_args(ws, spec, p.get("arg_descs"))
        t_exec0 = time.time()
        if spec.is_actor_creation:
            cls = _load_fn(ws, spec.fn_blob)
            ws.actor_instance = cls(*args, **kwargs)
            ws.actor_id = spec.actor_id
            results = [ws.client.put_result(result_oids[0], None)]
        else:
            if spec.actor_id is not None:
                fn = getattr(ws.actor_instance, spec.method_name)
            else:
                fn = _load_fn(ws, spec.fn_blob)
            out = _call(ws, fn, args, kwargs)
            if spec.num_returns == "streaming":
                results = [_drain_generator(ws, spec, result_oids[0], out)]
            elif spec.num_returns == 1:
                results = [ws.client.put_result(result_oids[0], out)]
            else:
                seq = tuple(out)
                if len(seq) != spec.num_returns:
                    raise ValueError(
                        f"task declared num_returns={spec.num_returns} but returned "
                        f"{len(seq)} values")
                results = [ws.client.put_result(oid, v) for oid, v in zip(result_oids, seq)]
    except _ActorExit:
        ws.client.notify_actor_exit(ws.actor_id)
        ws.client._send("task_done", task_id=spec.task_id, results=[], error=None)
        sys.exit(0)
    except KeyboardInterrupt:
        error = exc.TaskCancelledError(spec.task_id)
    except BaseException as e:  # noqa: BLE001 - full fidelity to the caller
        tb = traceback.format_exc()
        error = exc.TaskError(spec.name or str(spec.method_name or "task"), tb, e)
    finally:
        ws.client.current_task_id = None
        if traced:
            tracing.set_current(None, None)
    t_done = time.time()
    span = None
    if traced:
        # (resolve start, exec start, exec end): the controller folds these
        # into the task's exec/publish phase spans; the local ring keeps a
        # worker-side copy for per-process debugging
        span = (t_res0, t_exec0, t_done)
        tracing.record_span("worker.resolve_args", "worker", spec.trace_id,
                            tracing.new_span_id(), spec.parent_span_id,
                            t_res0, t_exec0 - t_res0,
                            args={"task_id": spec.task_id})
        tracing.record_span("worker.exec", "worker", spec.trace_id,
                            tracing.new_span_id(), spec.parent_span_id,
                            t_exec0, t_done - t_exec0,
                            args={"task_id": spec.task_id})
    # app spans queued via tracing.ship_window during exec (e.g. the MPMD
    # pipeline stages' fwd/bwd windows) piggyback on this completion frame
    # — the worker ring itself is never drained by any heartbeat
    shipped = tracing.take_shipped() or None
    # fire-and-forget: rides the ordered batch flusher behind this task's
    # puts (legacy direct frame when prefetching dispatch is off)
    ws.client.send_task_done(spec.task_id, results, error, span, shipped)


def _drain_generator(ws, spec, handle_oid, gen):
    """Stream yielded values as they materialize (ref: _raylet.pyx
    execute_streaming_generator)."""
    item_oids = []
    if inspect.isasyncgen(gen):
        loop = ws.get_async_loop()

        async def drain():
            out = []
            async for item in gen:
                out.append(_emit(ws, spec, item))
            return out

        item_oids = asyncio.run_coroutine_threadsafe(drain(), loop).result()
    else:
        for item in gen:
            item_oids.append(_emit(ws, spec, item))
    return ws.client.put_result(handle_oid, item_oids)


def _emit(ws, spec, item):
    oid = ids.object_id()
    _, meta_len, size, inline, contained = ws.client.put_result(oid, item)
    ws.client._send("stream_item", task_id=spec.task_id, oid=oid,
                    meta_len=meta_len, size=size, inline=inline,
                    contained=contained)
    return oid


def main():
    # SIGUSR1 → dump all thread stacks to stderr (ref: ray's faulthandler
    # setup in default_worker.py); invaluable for hung-worker debugging
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    # structured logging: the driver published its LoggingConfig via env
    # (ref: python/ray/_private/ray_logging/logging_config.py applied in
    # default_worker.py)
    from ray_tpu.logging_config import apply_from_env
    apply_from_env()
    # runtime_env working_dir: the controller staged a copy and points us at
    # it (ref: working_dir semantics in python/ray/_private/runtime_env)
    wd = os.environ.get("RAY_TPU_WORKING_DIR")
    if wd and os.path.isdir(wd):
        os.chdir(wd)
    socket_path, worker_id = sys.argv[1], sys.argv[2]
    client = WorkerClient(socket_path, worker_id)
    state.set_global_client(client)
    ws = WorkerState(client)
    state.set_worker_state(ws)
    # actors declare their real parallelism; plain-task workers keep the
    # old 64-thread ceiling (the controller's CPU accounting is the real cap)
    try:
        max_workers = max(1, int(os.environ.get("RAY_TPU_MAX_CONCURRENCY",
                                                "64")))
    except ValueError:
        max_workers = 64
    pool = ThreadPoolExecutor(max_workers=max_workers,
                              thread_name_prefix="rtpu-exec")
    while True:
        with client.task_available:
            while not client.task_queue:
                client.task_available.wait()
            p = client.task_queue.pop(0)
        if p is None:
            break
        pool.submit(_execute, ws, p)
        _warm_next(ws)
    # The controller's socket closed: the driver is gone (killed, or it shut
    # down and took its controller along), and whatever still runs here runs
    # for nobody. Do not wait for it: an actor's method that never returns, or
    # a thread of its own, would keep this process, and the chip it holds,
    # for ever (`pool.shutdown(wait=True)` did, and the interpreter's exit
    # joins every non-daemon thread besides). Flush what is buffered (best
    # effort: with the controller gone it is a no-op) and end the process.
    pool.shutdown(wait=False, cancel_futures=True)
    try:
        client.close()
    finally:
        os._exit(0)


if __name__ == "__main__":
    main()
