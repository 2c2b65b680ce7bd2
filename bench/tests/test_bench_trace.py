"""The trace reduction, on hand-made planes laid out as a TPU trace is:
device planes with ``XLA Ops`` and ``XLA Modules`` lines, the benchmark's
spans on the host plane; and on ``data/v5e_trace.json.gz``, a small trace
that ``record_trace.py`` recorded on one TPU v5e."""
import os

import pytest

from bench import trace


def _planes(ops0, ops1=None, host=()):
    def dev(i, ops):
        return {"name": f"/device:TPU:{i}", "lines": {
            trace.OPS_LINE: [(n, s, e - s) for n, s, e in ops],
            trace.MODULES_LINE: [("jit_step(1)", 0.0, 1000.0)]}}

    planes = [{"name": "/host:CPU", "lines": {"python": [
        (trace.WINDOW_SPAN, 100.0, 900.0)] + [(n, s, e - s) for n, s, e in host]}}]
    planes.append(dev(0, ops0))
    if ops1 is not None:
        planes.append(dev(1, ops1))
    return planes


def test_merge_and_subtract():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_busy_union_idle_share_and_window_clip():
    # Overlapping ops count once; ops outside [100, 1000) are clipped.
    p = _planes([("fusion.1", 50.0, 300.0), ("fusion.2", 250.0, 400.0),
                 ("convolution.3", 600.0, 700.0), ("fusion.4", 950.0, 1200.0)])
    r = trace.reduce(p)
    assert r["window_s"] == pytest.approx(900e-9)
    assert r["busy_s"] == pytest.approx((300 + 100 + 50) * 1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(450 / 900)


def test_exposed_collective_and_mean_over_chips():
    # Chip 0: all-reduce 300-500 overlaps compute until 400 -> 100 exposed.
    # Chip 1: all-gather 300-500 with no compute -> 200 exposed.
    p = _planes([("fusion.1", 200.0, 400.0), ("all-reduce.7", 300.0, 500.0)],
                [("all-gather-start.2", 300.0, 500.0)])
    r = trace.reduce(p)
    assert r["chips"] == 2
    assert r["exposed_collective_s"] == pytest.approx(150e-9)
    assert r["busy_s"] == pytest.approx((300 + 200) / 2 * 1e-9)


def test_breakdown_by_op_and_by_host_activity():
    p = _planes([("fusion.1", 100.0, 400.0), ("fusion.22", 500.0, 600.0),
                 ("convolution.3", 800.0, 900.0)],
                host=[("bench.batch", 380.0, 520.0), ("bench.land", 550.0, 1000.0),
                      ("bench.inner", 600.0, 800.0)])
    b = trace.reduce(p)["breakdown"]
    assert b["device_ops"][0] == ["jit_step/fusion", pytest.approx(400e-9)]
    assert b["device_ops"][1] == ["jit_step/convolution", pytest.approx(100e-9)]
    # Gaps: 400-500 (batch), 600-800 (innermost open span: inner), 900-1000 (land).
    assert b["idle_gaps"] == [["bench.inner", pytest.approx(200e-9)],
                              ["bench.batch", pytest.approx(100e-9)],
                              ["bench.land", pytest.approx(100e-9)]]


def test_breakdown_counts_a_loop_through_its_body():
    p = _planes([("while.1", 100.0, 500.0), ("fusion.2", 100.0, 300.0),
                 ("custom-call.3", 300.0, 450.0), ("fusion.4", 600.0, 700.0)])
    r = trace.reduce(p)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert dict(r["breakdown"]["device_ops"]) == {
        "jit_step/fusion": pytest.approx(300e-9), "jit_step/custom-call": pytest.approx(150e-9)}


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(_planes([]))


def test_recorded_v5e_trace():
    """Three steps of a 5 ms host sleep, then a jitted bf16 matmul chain of
    two fusions (about 90 us each) on one chip."""
    planes = trace.load_planes(os.path.join(os.path.dirname(__file__), "data",
                                            "v5e_trace.json.gz"))
    (dev,) = trace.device_planes(planes)
    ops = dev["lines"][trace.OPS_LINE]
    fused = [d for n, _, d in ops if n.startswith(("%fusion ", "%convolution_tanh_fusion "))]
    assert len(fused) == 6
    r = trace.reduce(planes)
    assert r["chips"] == 1
    t0, t1 = trace.window(planes)
    assert r["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    # Busy: the union of every op interval; the six fusions are nearly all of it.
    assert r["busy_s"] == pytest.approx(sum(fused) * 1e-9, rel=1e-3)
    assert 0.9 < 1 - r["busy_s"] / r["window_s"] < 1.0
    assert r["exposed_collective_s"] == 0.0  # one chip: no collective ran
    b = r["breakdown"]
    names = [k for k, _ in b["device_ops"]]
    assert set(names[:2]) == {"jit__lambda/fusion", "jit__lambda/convolution_tanh_fusion"}
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(r["busy_s"], rel=1e-3)
    # The longest idle gaps are the host's sleeps: the two between steps
    # hold a whole 5 ms sleep; the first is cut by the window's start.
    assert [k for k, _ in b["idle_gaps"][:3]] == ["bench.host_wait"] * 3
    assert min(v for _, v in b["idle_gaps"][:2]) >= 0.005
    gaps = sum(v for _, v in b["idle_gaps"])
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-6)
