"""The trace reduction, on a hand-made trace and on one recorded here."""
import pytest

import trace_reduce as tr

MS = 1_000_000


def test_reduce_events_busy_ops_and_gaps():
    devices = {"/device:TPU:0": [("kern", 0, 4 * MS), ("kern", 2 * MS,
                                                         5 * MS),
                                 ("copy", 8 * MS, 9 * MS),
                                 ("late", 20 * MS, 30 * MS)]}
    host = [("bench.window", 0, 10 * MS), ("bench.run_pooled", 0, 6 * MS),
            ("bench.run_pooled", 6 * MS, 10 * MS),
            ("PjitFunction(f)", 5 * MS, 7 * MS)]
    out = tr.reduce_events(devices, host, (0, 10 * MS))
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.006)         # [0,5) and [8,9)
    assert out["op_seconds"] == pytest.approx({"kern": 0.007, "copy": 0.001})
    assert out["device_ops"][0] == ["kern", pytest.approx(0.007)]
    gaps = dict((k, v) for k, v in out["idle_gaps"])
    # [5,8): middle 6.5 ms in the second run_pooled; PjitFunction covers
    # 2 of its 3 ms.  [9,10): the second run_pooled only.
    assert gaps == {"bench.run_pooled > PjitFunction(f)": pytest.approx(0.003),
                    "bench.run_pooled > no host event": pytest.approx(0.001)}
    assert tr.kernel_seconds(out["op_seconds"], r"^ke") == pytest.approx(0.007)


def test_two_devices_average_and_empty_device():
    devices = {"/device:TPU:0": [("a", 0, 2 * MS)],
               "/device:TPU:1": [("a", 0, 4 * MS)],
               "/device:TPU:2": [("a", 50 * MS, 60 * MS)]}
    out = tr.reduce_events(devices, [("bench.window", 0, 10 * MS)],
                           (0, 10 * MS))
    assert out["n_devices_busy"] == 2
    assert out["busy_s"] == pytest.approx(0.003)


def test_no_device_op_is_one_gap():
    out = tr.reduce_events({}, [("bench.window", 0, MS)], (0, MS))
    assert out["busy_s"] == 0.0
    assert out["idle_gaps"] == [["no device op in the window",
                                 pytest.approx(0.001)]]


def test_union():
    import numpy as np
    u = tr.union(np.array([[5, 6], [0, 2], [1, 3], [3, 4]]))
    assert u.tolist() == [[0, 4], [5, 6]]


def test_recorded_trace_finds_the_window(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.job"):
            jnp.arange(1000.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    out = tr.reduce_trace(str(tmp_path))
    assert out["window_s"] > 0
    # the CPU has no device plane: no busy time, one labelled gap
    assert out["busy_s"] == 0.0
    assert out["idle_gaps"][0][0] == "no device op in the window"
