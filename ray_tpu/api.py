"""Top-level API (reference: python/ray/_private/worker.py public functions +
python/ray/__init__.py exports).

`init()` starts the single-host controller on a background asyncio thread;
TPU chips are first-class resources ("TPU"), counted from the host's device
nodes — neither the driver nor a worker imports jax to find them.
"""

import asyncio
import atexit
import inspect
import os
import threading

from ._private import ids, paths, state
from ._private.client import DriverClient, WorkerClient
from ._private.controller import Controller, DEFAULT_CAPACITY
from ._private.object_ref import ObjectRef, ObjectRefGenerator
from .actor import ActorClass, ActorHandle
from .remote_function import RemoteFunction
from .util import tpu
from . import exceptions as exc

_runtime = None
_lock = threading.Lock()


class _Runtime:
    def __init__(self, controller, loop, thread, client, namespace):
        self.controller = controller
        self.loop = loop
        self.thread = thread
        self.client = client
        self.namespace = namespace


def is_initialized() -> bool:
    return _runtime is not None


def init(num_cpus=None, num_tpus=None, resources=None, namespace=None,
         object_store_memory=None, ignore_reinit_error=False, max_workers=None,
         address=None, session_name=None, cluster_port=None,
         logging_config=None, **_compat):
    """Start the ray_tpu runtime in this process (the driver), or — with
    `address` — ATTACH to a session another process started (reference:
    ray.init(address="auto") / address=<endpoint>). `address` is the
    controller's unix socket path, or "auto" to read RAY_TPU_ADDRESS (set by
    the owning session and inherited by its workers and submitted jobs).

    `cluster_port` makes this driver a cluster HEAD (ref: `ray start
    --head --port=N`): worker hosts join with
    `python -m ray_tpu._private.node_main --address <host>:<port>` and their
    CPUs/TPUs become schedulable (see _private/cluster.py). 0 picks an
    ephemeral port; read the bound address via `ray_tpu.cluster_address()`.

    Unrecognized reference kwargs (dashboard_*, logging_*) are accepted and
    ignored for drop-in compatibility.
    """
    global _runtime
    with _lock:
        if _runtime is not None:
            if ignore_reinit_error:
                return
            raise RuntimeError("ray_tpu.init() called twice; pass ignore_reinit_error=True.")
        if logging_config is not None:
            # configure the driver AND publish for every worker this
            # session spawns (workers inherit the driver's environ)
            logging_config.publish_to_env()
            logging_config.apply()
        else:
            # a PREVIOUS session's published config must not leak into
            # this one's workers (init→shutdown→init without the kwarg)
            os.environ.pop("RAY_TPU_LOGGING_CONFIG", None)
        if address is not None:
            sock = os.environ.get("RAY_TPU_ADDRESS") if address == "auto" else address
            if not sock or not os.path.exists(sock):
                raise ConnectionError(
                    f"no ray_tpu session at address {address!r} (socket {sock!r})")
            client = WorkerClient(sock, ids.worker_id(), driver=True)
            client.namespace = namespace or "default"
            state.set_global_client(client)
            _runtime = _Runtime(None, None, None, client, namespace or "default")
            atexit.register(shutdown)
            return
        total = dict(resources or {})
        total["CPU"] = float(num_cpus if num_cpus is not None else max(os.cpu_count(), 4))
        # counted from device nodes: the driver must never open the chip its
        # workers are about to be bound to (one process per libtpu chip)
        ntpu = (num_tpus if num_tpus is not None
                else tpu.count_local_chips())
        if ntpu:
            total["TPU"] = float(ntpu)
        total.setdefault("memory", 64 << 30)
        sock = os.path.join(paths.user_tmp_root(),
                            f"rtpu-{os.getpid()}-{ids.new_id('s')[-8:]}.sock")
        # publish the arena name BEFORE the controller builds its store;
        # workers inherit the env and attach to the same C++ shm arena
        capacity = object_store_memory or DEFAULT_CAPACITY
        os.environ["RAY_TPU_ARENA"] = f"rtpu-arena-{os.getpid()}-{ids.new_id('a')[-8:]}"
        os.environ["RAY_TPU_STORE_BYTES"] = str(capacity)
        # discoverable by children (workers, submitted job drivers) for
        # init(address="auto") attachment
        os.environ["RAY_TPU_ADDRESS"] = sock
        # GCS fault tolerance: a NAMED session journals detached actors and
        # spilled objects to a per-name directory; a later init() with the
        # same name restores them (ref: GCS FT; see _private/gcs.py)
        session_dir = None
        if session_name:
            # a bare name, not a path: keeps the journal under the verified
            # per-user root (session_name="/shared/x" or "../x" would escape
            # the 0700 boundary the journal's trust model depends on)
            if (os.sep in session_name or session_name in (".", "..")
                    or (os.altsep and os.altsep in session_name)):
                raise ValueError(
                    f"session_name must be a plain name, got {session_name!r}")
            session_dir = os.path.join(paths.subdir("sessions"), session_name)
        controller = Controller(
            sock, total, job_id=ids.job_id(),
            max_workers=max_workers,
            store_capacity=capacity,
            session_dir=session_dir,
            cluster_port=cluster_port)

        loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(loop)
            loop.run_until_complete(controller.start())
            started.set()
            loop.run_forever()

        thread = threading.Thread(target=run, daemon=True, name="rtpu-controller")
        thread.start()
        started.wait(10)
        client = DriverClient(controller, loop)
        client.namespace = namespace or "default"
        state.set_global_client(client)
        _runtime = _Runtime(controller, loop, thread, client, namespace or "default")
        atexit.register(shutdown)
        return


def shutdown():
    global _runtime
    with _lock:
        if _runtime is None:
            return
        rt, _runtime = _runtime, None
        if rt.controller is None:
            # attached driver: just drop the connection; the owning session
            # reconciles our handle refs via the worker-death path
            try:
                rt.client.close()
            except Exception:  # noqa: BLE001
                pass
            state.set_global_client(None)
            return
        try:
            # drain batched refcount/put deltas first: pending decrefs apply
            # before the controller audits its object table, so shutdown
            # never reports refs the driver already dropped
            rt.client.flush()
        except Exception:  # noqa: BLE001
            pass
        try:
            fut = asyncio.run_coroutine_threadsafe(rt.controller.shutdown(), rt.loop)
            fut.result(10)
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        def _stop():
            for t in asyncio.all_tasks(rt.loop):
                t.cancel()
            rt.loop.call_soon(rt.loop.stop)

        rt.loop.call_soon_threadsafe(_stop)
        rt.thread.join(5)
        state.set_global_client(None)


def _ensure_init():
    # auto-init only in a bare driver; workers already carry a WorkerClient
    if state.global_client_or_none() is None:
        init()


def remote(*args, **options):
    """@remote decorator for functions and classes (ref:
    python/ray/_private/worker.py:remote)."""

    def wrap(target):
        if inspect.isclass(target):
            return ActorClass(target, **options)
        return RemoteFunction(target, **options)

    if len(args) == 1 and callable(args[0]) and not options:
        return wrap(args[0])
    if args:
        raise TypeError("@remote takes keyword options only, e.g. @remote(num_tpus=1)")
    return wrap


def object_ref_from_id(object_id: str) -> "ObjectRef":
    """Rebuild an ObjectRef from its string id (reference:
    ObjectRef(binary_hex)). The session-restore path: save `ref.id` before a
    controller restart, re-init with the same `session_name`, and the
    restored spilled object resolves through this handle."""
    return ObjectRef(object_id, owned=False)


def get(refs, *, timeout=None):
    _ensure_init()
    client = state.global_client()
    if isinstance(refs, ObjectRef):
        return client.get([refs.id], timeout=timeout)[0]
    if isinstance(refs, ObjectRefGenerator):
        raise TypeError("get() on a streaming generator; iterate it instead.")
    if not isinstance(refs, (list, tuple)):
        raise TypeError(f"get() expects ObjectRef or list, got {type(refs)}")
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() list elements must be ObjectRef, got {type(r)}")
    if not refs:
        return []
    return client.get([r.id for r in refs], timeout=timeout)


def put(value) -> ObjectRef:
    _ensure_init()
    if isinstance(value, ObjectRef):
        raise TypeError("put() of an ObjectRef is not allowed (matches reference).")
    return ObjectRef(state.global_client().put(value), owned=True)


def wait(refs, *, num_returns=1, timeout=None, fetch_local=True):
    _ensure_init()
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs.")
    if num_returns > len(refs):
        raise ValueError(f"num_returns={num_returns} > len(refs)={len(refs)}")
    by_id = {r.id: r for r in refs}
    ready_ids, rest_ids = state.global_client().wait(
        [r.id for r in refs], num_returns, timeout)
    return [by_id[i] for i in ready_ids], [by_id[i] for i in rest_ids]


def cancel(ref, *, force=False, recursive=True):
    _ensure_init()
    target = ref.id if isinstance(ref, ObjectRef) else str(ref)
    state.global_client().cancel(target, force=force)


def kill(actor, *, no_restart=True):
    _ensure_init()
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle; use cancel() for tasks.")
    state.global_client().kill_actor(actor._actor_id, no_restart=no_restart)


def get_actor(name, namespace=None) -> ActorHandle:
    _ensure_init()
    client = state.global_client()
    actor_id = client.get_actor(name, namespace or getattr(client, "namespace", None))
    # method metadata lives with the creating driver; reconstruct lazily
    meta = _actor_method_meta(actor_id)
    return ActorHandle(actor_id, meta, name=name)


def _actor_method_meta(actor_id):
    client = state.global_client()
    if getattr(client, "is_driver", False) and hasattr(client, "controller"):
        actor = client.controller.actors.get(actor_id)
        if actor is not None and actor.creation_spec is not None:
            import cloudpickle
            cls = cloudpickle.loads(actor.creation_spec.fn_blob)
            return ActorClass(cls)._method_meta()
    return _AnyMethodMeta()


class _AnyMethodMeta(dict):
    """Workers can't read the controller's class blob cheaply; allow any
    method name and let the actor-side getattr fail loudly."""

    def get(self, key, default=None):
        return {"num_returns": 1}


def available_resources():
    _ensure_init()
    return state.global_client().resources()[1]


def cluster_resources():
    _ensure_init()
    return state.global_client().resources()[0]


def nodes():
    _ensure_init()
    return state.global_client().state("nodes")


def cluster_address():
    """The head's TCP endpoint ("host:port") when this driver was started
    with init(cluster_port=...); None otherwise. Worker hosts join with
    `python -m ray_tpu._private.node_main --address <this>`."""
    _ensure_init()
    ctl = getattr(_runtime, "controller", None)
    if ctl is None or ctl.cluster is None:
        return None
    return ctl.cluster.address


def timeline(filename=None):
    """Chrome-trace task timeline (ref: ray.timeline)."""
    _ensure_init()
    events = state.global_client().timeline()
    if filename:
        import json
        with open(filename, "w") as f:
            json.dump(events, f)
    return events
