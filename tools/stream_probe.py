"""What the stream path carries, with no model behind it.

A deployment whose async generator yields bare integers, one replica, and
`--streams` reader threads that iterate the handle's generator the way
`perfbench/client.py Client._read` does. Every reader opens its next stream
as soon as the last one ends, so `--streams` streams are open throughout.
`--tick-ms 0` lets the producer run as fast as it can; a tick of t ms offers
streams x 1000 / t items a second.

Prints one JSON line: items/s at the readers, the CPU seconds a second of
THIS process (it holds the controller's loop and every reader thread), and
items a read from the runtime's own counters (`control_plane_counters()`
["streams"]; absent on a tree from before the batch read, then null).

    python3 tools/stream_probe.py --streams 144 --tick-ms 10 --seconds 10

Host-only: no jax, no chip. A CPU measurement of the runtime's host path;
not a tier-1 test and never a device number.
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("RAY_TPU_NUM_CHIPS", "0")

_ITEMS_PER_STREAM = 256


def _stream_counters():
    from ray_tpu.util import metrics
    return dict(metrics.control_plane_counters().get("streams") or {})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--streams", type=int, default=96)
    ap.add_argument("--tick-ms", type=float, default=0.0,
                    help="producer's pause between a stream's items (0: none)")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)

    @serve.deployment(max_ongoing_requests=4 * args.streams)
    class Integers:
        async def stream(self, n, tick_s):
            import asyncio
            for i in range(n):
                # sleep(0) still yields the loop, as an engine's queue does
                await asyncio.sleep(tick_s)
                yield i

    handle = serve.run(Integers.bind(), name="stream_probe")
    streamer = handle.options(method_name="stream", stream=True)
    assert list(streamer.remote(3, 0.0)) == [0, 1, 2]  # warm the path

    counts = [0] * args.streams
    stop = threading.Event()

    def read(slot):
        while not stop.is_set():
            for _item in streamer.remote(_ITEMS_PER_STREAM, args.tick_ms / 1e3):
                counts[slot] += 1

    threads = [threading.Thread(target=read, args=(i,), daemon=True)
               for i in range(args.streams)]
    for t in threads:
        t.start()
    time.sleep(min(2.0, args.seconds / 4))  # every stream open before timing
    before, c0 = _stream_counters(), sum(counts)
    t0, cpu0 = time.monotonic(), time.process_time()
    time.sleep(args.seconds)
    wall, cpu = time.monotonic() - t0, time.process_time() - cpu0
    items, after = sum(counts) - c0, _stream_counters()
    stop.set()
    reads = after.get("reads", 0) - before.get("reads", 0)
    print(json.dumps({
        "streams": args.streams, "tick_ms": args.tick_ms,
        "seconds": round(wall, 3), "items_per_s": round(items / wall, 1),
        "driver_cpu_share": round(cpu / wall, 3),
        "items_per_read": (round((after["items"] - before["items"]) / reads, 3)
                           if reads else None)}), flush=True)
    # readers are daemons mid-stream: leave without draining them
    os._exit(0)


if __name__ == "__main__":
    main()
