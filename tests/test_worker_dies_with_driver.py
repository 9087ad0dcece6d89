"""A worker does not outlive its driver: when the driver is killed outright
(`SIGKILL`: no shutdown hook runs), the worker processes it spawned are gone
within seconds, whatever their actors' threads are doing. A benchmark run the
harness stops at its time limit must not leave the process that holds the
chip."""

import os
import signal
import subprocess
import sys
import textwrap
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER = textwrap.dedent("""
    import os, sys, threading, time
    import ray_tpu

    @ray_tpu.remote
    class Spinner:
        def __init__(self):
            self.ticks = 0
            # a never-ending loop on a thread of the actor's own, not a daemon
            self.thread = threading.Thread(target=self._spin)
            self.thread.start()

        def _spin(self):
            while True:
                self.ticks += 1
                time.sleep(0.01)

        def forever(self):          # and a method call that never returns
            while True:
                time.sleep(0.05)

        def pid(self):
            return os.getpid()

    ray_tpu.init(num_cpus=2)
    a = Spinner.options(max_concurrency=4).remote()
    pid = ray_tpu.get(a.pid.remote())
    a.forever.remote()
    time.sleep(0.3)
    print("WORKER_PID", pid, flush=True)
    time.sleep(600)
""")


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    # a zombie still answers signal 0: it is gone for our purpose
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def test_worker_is_gone_within_10_s_of_its_drivers_sigkill(tmp_path):
    env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""), RAY_TPU_NUM_CHIPS="0")
    env.pop("RAY_TPU_ADDRESS", None)
    driver = subprocess.Popen([sys.executable, "-c", DRIVER], env=env,
                              stdout=subprocess.PIPE, text=True,
                              cwd=str(tmp_path))
    worker = None
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            line = driver.stdout.readline()
            if line.startswith("WORKER_PID"):
                worker = int(line.split()[1])
                break
            if not line and driver.poll() is not None:
                break
        assert worker is not None, "the driver never reported its worker"
        assert _alive(worker)
        driver.send_signal(signal.SIGKILL)
        driver.wait(10)
        t0 = time.monotonic()
        while _alive(worker) and time.monotonic() - t0 < 10:
            time.sleep(0.1)
        assert not _alive(worker), (
            f"worker {worker} outlived its driver by more than 10 s")
    finally:
        if driver.poll() is None:
            driver.kill()
        if worker is not None and _alive(worker):
            os.kill(worker, signal.SIGKILL)
