#!/usr/bin/env bash
# Build every native control-plane extension ahead of time (the Python
# bindings also build on-demand; this script exists for CI images and for a
# visible one-shot "does the toolchain work" check).
#
#   shm_store      src/shm_store.cpp      — shared-memory object store arena
#   sched_queue    src/sched_queue.cpp    — ready-queue index
#   frame_codec    src/frame_codec.cpp    — wire-frame scanner/validator
#   obj_directory  src/obj_directory.cpp  — id-sharded object/actor directory
#
# Each target goes through its Python binding's _compile() so the cache key
# (mtime vs the cached .so under ray_tpu/_native/_build/) and the compiler
# flags stay defined in exactly one place. Exit code is the number of
# targets that failed; RAY_TPU_NATIVE=0 environments still pass --check.
set -u
cd "$(dirname "$0")/.."

MODE="${1:-build}"

python - "$MODE" <<'EOF'
import importlib
import sys

from ray_tpu._native import TARGETS

failed = 0
for name, mod in TARGETS.items():
    try:
        so = importlib.import_module(f"ray_tpu._native.{mod}")._compile()
        print(f"  [ok] {name:14s} -> {so}")
    except Exception as e:  # noqa: BLE001 - report and count
        failed += 1
        msg = str(e).replace("\n", " ")[:200]
        print(f"  [FAIL] {name:14s} {msg}")

if sys.argv[1] == "check" and failed:
    print(f"{failed} native target(s) unavailable "
          f"(pure-Python fallbacks will be used)")
sys.exit(failed)
EOF
