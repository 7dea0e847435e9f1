"""The trace reduction on a synthetic trace with known busy, idle and
kernel times."""
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from isla_bench import trace

MS = 1_000_000  # ns


def planes(device_lines=None):
    host = ("/host:CPU", [
        ("python3", [
            ("bench:window", 10 * MS, 100 * MS),          # [10, 110)
            ("bench:tick", 10 * MS, 60 * MS),             # [10, 70)
            ("bench:draw", 12 * MS, 20 * MS),             # [12, 32)
            ("bench:draw", 40 * MS, 10 * MS),             # [40, 50)
            ("bench:wait", 70 * MS, 40 * MS),             # [70, 110)
            ("PjitFunction(x)", 0, 5 * MS),               # not a span
        ]),
        ("launch", [("isla:launch", 50 * MS, 5 * MS)]),   # [50, 55)
    ])
    ops = [("fusion.1", 0, 15 * MS),          # [0, 15): 5 ms in window
           ("fusion.2", 32 * MS, 8 * MS),     # [32, 40)
           ("fusion.3", 36 * MS, 9 * MS),     # [36, 45): overlaps fusion.2
           ("copy.1", 55 * MS, 5 * MS)]       # [55, 60)
    mods = [("jit_fused_tick_dense(1)", 32 * MS, 13 * MS),
            ("jit_convert_element_type(2)", 55 * MS, 5 * MS)]
    dev = ("/device:TPU:0", device_lines if device_lines is not None else [
        ("XLA Ops", ops), ("XLA Modules", mods), ("Steps", [])])
    return [host, dev, ("/host:metadata", [])]


def test_busy_window_and_modules():
    s = trace.reduce_planes(planes())
    assert s.window_s == pytest.approx(0.100)
    # union inside [10, 110): [10, 15) + [32, 45) + [55, 60) = 23 ms
    assert s.busy_s == pytest.approx(0.023)
    assert s.module_seconds("fused_tick") == (pytest.approx(0.013), 1)
    assert s.module_seconds("jit_") == (pytest.approx(0.018), 2)
    assert s.ops["fusion.1"] == pytest.approx(0.005)
    assert s.n_devices == 1


def test_idle_gaps_go_to_the_innermost_host_span():
    s = trace.reduce_planes(planes())
    # idle: [15, 32) mid 23.5 in draw; [45, 55) mid 50 in launch;
    # [60, 110) mid 85 in wait.
    assert s.idle["bench:draw"] == pytest.approx(0.017)
    assert s.idle["isla:launch"] == pytest.approx(0.010)
    assert s.idle["bench:wait"] == pytest.approx(0.050)
    assert sum(s.idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_modules_line_stands_in_for_ops():
    mods = [("jit_fused_tick_dense(1)", 20 * MS, 10 * MS)]
    s = trace.reduce_planes(planes([("XLA Modules", mods)]))
    assert s.busy_s == pytest.approx(0.010)


def test_breakdown_is_capped_and_sorted():
    ops = [(f"op{i}", i * MS, (i + 1) * 100_000) for i in range(30)]
    p = planes([("XLA Ops", [(n, 10 * MS + s, d) for n, s, d in ops])])
    b = trace.breakdown(trace.reduce_planes(p))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0] == "op29"


def test_no_window_span_is_an_error():
    p = planes()
    p[0] = ("/host:CPU", [("python3", [("bench:tick", 0, MS)])])
    with pytest.raises(ValueError):
        trace.reduce_planes(p)
