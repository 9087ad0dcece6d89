"""Native components (C++, ctypes-bound). Built on demand with g++; every
module here degrades gracefully to a pure-python fallback when the toolchain
is missing."""

import os
import subprocess

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")

# target name -> binding module (each exposes _compile())
TARGETS = {"shm_store": "store", "sched_queue": "schedq",
           "frame_codec": "codec", "obj_directory": "objdir"}


def build(name: str, *link_flags: str) -> str:
    """Path of lib<name>.so, compiled from src/<name>.cpp when missing or
    older than its source. A fresh checkout has no .so, and the driver and
    its workers may all get here at once: each compiles to an output of its
    own and renames it into place, so nobody ever loads a half-written
    library and the last identical copy wins."""
    src = os.path.join(_SRC_DIR, f"{name}.cpp")
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so = os.path.join(_BUILD_DIR, f"lib{name}.so")
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    proc = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp,
         *link_flags], capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"{name} build failed: {proc.stderr[:2000]}")
    os.replace(tmp, so)
    return so


def build_report() -> str:
    """One row saying, per native target, built or fallback (with why) —
    a box without a toolchain still runs, it just says so here instead of
    silently using the Python twins."""
    import importlib
    rows = []
    for name, mod in TARGETS.items():
        try:
            importlib.import_module(f"{__name__}.{mod}")._compile()
            rows.append(f"{name}=built")
        except Exception as e:  # noqa: BLE001 - the fallback itself is the signal
            rows.append(f"{name}=FALLBACK({str(e)[:60].strip()})")
    return " ".join(rows)
