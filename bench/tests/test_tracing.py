"""The trace reduction from a profiler trace to busy time, op sums and idle
gaps named by the host span."""
import pytest

import tracing
from tracing import Events, Span, reduce_trace


def _events():
    # two devices; host marker at trace time 1_000 ns = host time 10.0 s
    ms = 1e6
    ops = {
        "/device:TPU:0": [("fusion.1", 1_000 + 0 * ms, 1_000 + 2 * ms),
                          ("event_fuse_occ", 1_000 + 1 * ms, 1_000 + 3 * ms),
                          ("fusion.2", 1_000 + 6 * ms, 1_000 + 7 * ms)],
        "/device:TPU:1": [("fusion.1", 1_000 + 0 * ms, 1_000 + 4 * ms)],
    }
    return Events(ops, mark_ns=1_000)


def test_busy_union_op_sums_and_idle_gaps():
    spans = [Span("entry", 10.0, 10.010), Span("engine", 10.0005, 10.0065)]
    r = reduce_trace(_events(), 10.0, 10.010, spans, mark_host=10.0)
    assert r.window_s == pytest.approx(0.010)
    assert r.busy_s["/device:TPU:0"] == pytest.approx(0.004)  # [0,3] + [6,7] ms
    assert r.busy_s["/device:TPU:1"] == pytest.approx(0.004)
    assert r.mean_busy_s == pytest.approx(0.004)
    assert r.op_s["fusion.1"] == pytest.approx(0.006)
    assert r.op_n["fusion.1"] == 2 and r.op_n["event_fuse_occ"] == 1
    # gaps on device 0: [3, 6] ms inside the engine span, [7, 10] ms after it
    assert sorted(r.idle_gaps) == [("engine", pytest.approx(0.003)),
                                   ("entry", pytest.approx(0.003))]
    b = tracing.breakdown(r)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]
    assert len(b["idle_gaps"]) == 2


def test_slice_clips_ops_and_unmarked_trace_is_empty():
    r = reduce_trace(_events(), 10.0015, 10.0025, [], mark_host=10.0)
    assert r.busy_s["/device:TPU:0"] == pytest.approx(0.001)
    assert r.op_s["event_fuse_occ"] == pytest.approx(0.001)
    empty = reduce_trace(Events(_events().device_ops, None), 10.0, 10.01, [], 10.0)
    assert not empty.busy_s and not empty.op_s


def test_recorded_trace_has_the_marker(tmp_path):
    import jax
    import jax.numpy as jnp

    spans = tracing.Spans()
    prof = tracing.Profile(str(tmp_path), cap_s=30)
    h = spans.open("entry")
    jax.jit(lambda x: jnp.cumsum(x) * 2)(jnp.ones(1000)).block_until_ready()
    spans.close(h)
    ev = tracing.read_trace(prof.finish())
    assert ev.mark_ns is not None
    assert prof.t1 > prof.t0


def test_control_flow_ops_are_not_busy_time():
    ms = 1e6
    ops = {"/device:TPU:0": [
        ("%while.10 = (s32[]{:T(128)}, s32[500]{0:T(512)}) while((s32[]) %t), "
         "condition=%c, body=%b", 0, 10 * ms),
        ("%cond.12 = (s32[]{:T(128)}) conditional(s32[]{:T(128)} %x)", 1 * ms, 9 * ms),
        ("%fusion.95 = pred[11200]{0:T(1024)} fusion(s32[11200]{0:T(1024)} %g), "
         "kind=kCustom, calls=%f", 2 * ms, 4 * ms),
    ]}
    r = reduce_trace(Events(ops, 0.0), 0.0, 0.010, [], mark_host=0.0)
    assert r.busy_s["/device:TPU:0"] == pytest.approx(0.002)
    assert list(r.op_s) == [ops["/device:TPU:0"][2][0]]
    assert tracing.breakdown(r)["device_ops"] == [["%fusion.95 fusion", pytest.approx(0.002)]]
