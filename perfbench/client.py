"""The load generator's client side: sends each request through the serve
handle, streams its tokens back with `generate_stream`, and stamps every token
with `time.monotonic()` as it reaches the client. One sender (the caller's
thread) and one reader thread per open stream, which sleeps in the stream's
`next()`; no jax.
"""

import threading
import time

_now = time.monotonic


class Client:
    def __init__(self, handle):
        self._stream = handle.options(stream=True)
        self.records = []
        self._threads = []

    def send(self, req: dict, due: float, on_done=None) -> dict:
        """Send now; `due` is when the request should have gone (absolute)."""
        rec = {"rid": req["rid"], "kind": req["kind"], "due": due,
               "n_prompt": len(req["prompt"]), "max_tokens": req["max_tokens"],
               "token_times": [], "error": None, "done": None, "sent": _now()}
        try:
            stream = self._stream.generate_stream.remote(
                req["rid"], req["prompt"], req["max_tokens"])
        except Exception as e:  # noqa: BLE001 - a refused request is a failed one
            rec["error"], rec["done"] = repr(e)[:300], _now()
            self.records.append(rec)
            if on_done:
                on_done(rec)
            return rec
        thread = threading.Thread(target=self._read, args=(stream, rec, on_done),
                                  daemon=True)
        self.records.append(rec)
        self._threads.append(thread)
        thread.start()
        return rec

    @staticmethod
    def _read(stream, rec, on_done):
        try:
            for _tok in stream:
                rec["token_times"].append(_now())
        except Exception as e:  # noqa: BLE001 - counted in `failed`, never raised
            rec["error"] = repr(e)[:300]
        rec["done"] = _now()
        if on_done:
            on_done(rec)

    def join(self, timeout_s: float) -> int:
        """Wait for every open stream; returns how many are still open."""
        deadline = _now() + timeout_s
        for t in self._threads:
            t.join(max(0.0, deadline - _now()))
        return sum(t.is_alive() for t in self._threads)


def run_open(client: Client, requests, t_open: float, seconds: float) -> None:
    """Open loop: each request goes at its due time whether or not earlier
    ones have finished; a request is never sent early."""
    for req in requests:
        due = t_open + req["due_s"]
        wait = due - _now()
        if wait > 0:
            time.sleep(wait)
        client.send(req, due)
    rest = t_open + seconds - _now()
    if rest > 0:
        time.sleep(rest)


def run_closed(client: Client, requests, in_flight: int, n_ramp: int,
               seconds: float, on_open=None) -> dict:
    """Closed loop with `in_flight` requests outstanding. The window opens when
    every one of the first `n_ramp` requests has completed, and closes
    `seconds` later. Returns the window's edges and how many requests of the
    list were offered."""
    free = threading.Semaphore(in_flight)
    ramp_left = [n_ramp]
    state = {"t_open": None}
    lock = threading.Lock()

    def on_done(rec):
        if rec["kind"] == "ramp":
            with lock:
                ramp_left[0] -= 1
                if ramp_left[0] == 0:
                    state["t_open"] = _now()
        free.release()

    if n_ramp == 0:
        state["t_open"] = _now()
    offered, opened = 0, False
    while True:
        t_open = state["t_open"]
        if t_open is not None and not opened:
            opened = True
            if on_open:
                on_open(t_open)
        if t_open is not None and _now() >= t_open + seconds:
            break
        if offered >= len(requests):
            time.sleep(0.01)         # drained: the caller reports it
            continue
        if free.acquire(timeout=0.02):
            client.send(requests[offered], _now(), on_done)
            offered += 1
    return {"t_open": state["t_open"], "t_close": state["t_open"] + seconds,
            "offered": offered, "drained": offered >= len(requests)}
