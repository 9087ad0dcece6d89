"""`trace_reduce.py` on hand-made traces (exact arithmetic) and on the small
trace recorded on a TPU v5e by `record_trace.py`, kept beside this file."""

import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from perfbench import trace_reduce as tr

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _trace(ops, modules=(), host=()):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=list(ops)),
        NS(name="XLA Modules", events=list(modules))])
    cpu = NS(name="/host:CPU", lines=[NS(name="python3", events=list(host))])
    return NS(planes=[device, cpu])


def test_union_counts_overlap_once():
    starts, ends = np.array([0., 5., 20., 40.]), np.array([10., 8., 30., 41.])
    assert tr._union_seconds(starts, ends) == pytest.approx(21e-9)
    assert tr._union_seconds(np.array([]), np.array([])) == 0.0


def test_busy_window_tables_and_gaps():
    ops = [
        _ev("%while.3 = (s32[], bf16[8]{0}) while(%tuple.1), body=%b", 0, 100),
        _ev("%fusion.12 = bf16[32,14336]{1,0:T(8,128)(2,1)} fusion(%p.1)", 0, 40),
        _ev("paged_decode.7", 40, 20),                           # a bare name
        _ev("%all-gather.1 = f32[4096,4096]{1,0} all-gather(%p.2)", 60, 10),
        _ev("%fusion.13 = bf16[32,14336]{1,0:T(8,128)(2,1)} fusion(%p.3)", 200, 50),
    ]
    mods = [_ev("jit_decode_chunk(123)", 0, 100), _ev("jit_decode_chunk(123)", 200, 50)]
    host = [_ev("PjitFunction(decode_chunk)", 90, 120),
            _ev("np.asarray(jax.Array)", 95, 110)]
    out = tr.reduce(_trace(ops, mods, host))
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(150e-6)        # [0,100) + [200,250)
    assert out["window_s"] == pytest.approx(250e-6)
    assert out["ops"]["fusion_bf16_32_14336_"] == [2, pytest.approx(90e-6)]
    assert out["ops"]["paged_decode"][0] == 1
    assert not any(k.startswith("while") for k in out["ops"])
    assert out["collective_s"] == pytest.approx(10e-6)
    assert out["modules"]["jit_decode_chunk"] == [2, pytest.approx(150e-6)]
    # the one gap, [100, 200), goes to the innermost host event that covers it
    assert out["idle_gaps"] == [["np.asarray_jax.Array_", pytest.approx(100e-6)]]
    assert out["device_ops"][0][0] == "fusion_bf16_32_14336_"
    assert tr.seconds_of(out["ops"], "paged_decode", "all-gather") == (
        2, pytest.approx(30e-6))


def test_two_devices_average_busy_and_sum_tables():
    def plane(n):
        return NS(name=f"/device:TPU:{n}", lines=[NS(name="XLA Ops", events=[
            _ev("fusion.1", 0, 10 * (n + 1))])])
    out = tr.reduce(NS(planes=[plane(0), plane(1)]))
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(15e-6)
    assert out["ops"]["fusion"] == [2, pytest.approx(30e-6)]
    assert out["idle_gaps"] == []


def test_a_trace_without_device_operations_reduces_to_nothing():
    out = tr.reduce(NS(planes=[NS(name="/host:CPU", lines=[])]))
    assert out["devices"] == 0 and out["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_tpu_trace():
    out = tr.reduce_file(SMALL)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    count, seconds = tr.seconds_of(out["modules"], "small_step")
    assert count == 3 and 0 < seconds <= out["window_s"]
    assert out["device_ops"][0][0] == "convert_reduce_fusion_f32__"
    # the two long gaps lie between the three executions, while the host
    # waits for a result or dispatches the next call
    assert {g[0] for g in out["idle_gaps"][:1]} <= {
        "np.asarray_jax.Array_", "PjitFunction_small_step_"}
    assert sum(c for c, _ in out["ops"].values()) >= 3
