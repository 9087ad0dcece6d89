"""chip_smoke.py rehearsed on the CPU: the same phases through the same entry
points at `tiny` sizes, pallas in interpret mode, so the script that proves the
system on the chip cannot rot between chip runs. Run as a script it has no
such mode and fails without a chip."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for k in ("RAY_TPU_ARENA", "RAY_TPU_ADDRESS", "RAY_TPU_NUM_CHIPS"):
        env.pop(k, None)
    env.update(extra)
    return env


_REHEARSAL = textwrap.dedent("""
    import dataclasses, json, sys
    import chip_smoke
    sizes = dataclasses.replace(chip_smoke.CHIP, **{**dict(
        preset="tiny", platform="cpu", interpret=True,
        batch=2, seq=64, steps=3, flash_shape=(1, 64, 4, 2, 16),
        paged_shape=(2, 2, 16, 16), slots=4, max_seq_len=256,
        prompt_len=100, shared_prefix=64, max_tokens=8, requests=4,
        actor_timeout_s=200.0), **json.loads(sys.argv[1])})
    print("DEVICE", json.dumps(chip_smoke.run_phases(sizes, 1)))
    """)


def _rehearse(**overrides):
    import json
    return subprocess.run(
        [sys.executable, "-c", _REHEARSAL, json.dumps(overrides)],
        env=_env(RAY_TPU_NUM_CHIPS="1"), capture_output=True, text=True,
        timeout=420)


def test_phases_rehearse_on_cpu():
    r = _rehearse()
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-5000:]}"
    out = r.stdout
    assert 'DEVICE {"platform": "cpu"' in out
    # both phases ran in chip-bound actors, and printed no rate
    assert "[chip_smoke] train x1:" in out and "[chip_smoke] serve:" in out
    assert '"answered": "4/4"' in out and '"prefix_hit_tokens": 64' in out
    assert "tok/s" not in out and "tokens/s" not in out


def test_a_broken_phase_fails_the_run():
    """The actor sees the CPU where the phase was told to expect a TPU: the
    phase raises inside the worker, fit() hands it back in Result.error, and
    the smoke re-raises — no downgrade to a warning, no result."""
    r = _rehearse(platform="tpu")
    assert r.returncode != 0
    assert "chip-bound worker sees" in r.stderr
    assert "DEVICE" not in r.stdout


def test_script_fails_without_a_chip():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
