"""``bench/trace.py``: interval arithmetic on synthetic intervals, the
reading of TPU op names, and the reduction of a recorded CPU trace."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402

CPU_TRACE = ROOT / "bench" / "testdata" / "cpu_solve.xplane.pb"


def test_union_merges_overlaps_and_drops_empty():
    got = tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)])
    assert got == [(0, 3), (5, 9)]
    assert tr.measure(got) == 7


def test_subtract_and_gaps():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert tr.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.gaps(tr.union(b), 0, 30) == [(0, 2), (4, 8), (22, 25),
                                           (26, 30)]
    assert tr.gaps([], 3, 5) == [(3, 5)]


def test_busy_union_skips_containers_and_counts_overlap_once():
    ops = [("%while.1 = (f32[4]) while(f32[4] %t)", 0, 100),
           ("fusion.1", 10, 30), ("fusion.2", 20, 40), ("copy.3", 60, 70)]
    b = tr.busy(ops, 0, 100)
    assert b == [(10, 40), (60, 70)]
    assert tr.measure(b) == 40          # a plain sum would give 50
    # idle share over the window: 1 - 40/100
    assert 1 - tr.measure(b) / 100 == pytest.approx(0.6)


def test_exposed_collective_arithmetic():
    ops = [("fusion.1", 0, 10), ("all-reduce.2", 5, 20),
           ("fusion.3", 15, 18), ("collective-permute-done.4", 30, 34),
           ("fusion.5", 32, 40)]
    flying = [("%all-reduce-start.7 = f32[7] all-reduce-start(f32[7] %p)",
               40, 50)]
    # reduction runs 5..20 and 40..50; compute covers 5..10, 15..18
    assert tr.exposed(ops, "reduction", 0, 60, flying) == 5 + 2 + 10
    # halo runs 30..34, compute covers 32..34
    assert tr.exposed(ops, "halo", 0, 60) == 2
    assert tr.exposed(ops, "reduction", 0, 12) == 2      # clipped: 10..12


def test_parse_tpu_op_text():
    text = ("%multiply_reduce_fusion.14 = (f32[1000000]{0:T(1024)}, "
            "f32[3]{0}) fusion(f32[1000000,4]{0,1:T(4,128)S(1)} %g), "
            "kind=kLoop")
    assert tr.parse_op(text) == ("multiply_reduce_fusion.14", "fusion")
    plain = "%slice.3 = f32[999000]{0:T(1024)S(1)} slice(f32[1000000] %x)"
    assert tr.parse_op(plain) == ("slice.3", "slice")
    cond = "%cond.2.clone = (f32[4]{0}) conditional(s32[] %b, (f32[1]) %c)"
    assert tr.kind_of(cond) == "container"
    assert tr.kind_of("%ar.1 = f32[7] all-reduce-start(f32[7] %x)") \
        == "reduction"
    assert tr.kind_of("%cp.1 = f32[9] collective-permute(f32[9] %x)") \
        == "halo"


def test_summarize_synthetic_two_devices():
    host = [("solve", 0, 100), ("np.asarray(jax.Array)", 50, 100)]
    devices = {
        "/device:TPU:0": [("fusion.1", 0, 40), ("all-reduce.1", 40, 60),
                          ("fusion.2", 70, 100)],
        "/device:TPU:1": [("fusion.1", 0, 90), ("all-reduce.1", 80, 95)],
    }
    s = tr.summarize(tr.Trace(devices=devices, host=host), ("solve",))
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx((90 + 95) / 2 * 1e-9)
    assert s["reduction_exposed_s"] == pytest.approx((20 + 5) / 2 * 1e-9)
    assert s["idle_gaps"] == [["np.asarray(jax.Array)",
                               pytest.approx(10e-9)]]
    assert [n for n, _ in s["device_ops"]] == ["fusion.1", "all-reduce.1",
                                              "fusion.2"]


def test_summarize_without_spans_or_ops_finds_nothing():
    assert tr.summarize(tr.Trace(devices={}, host=[("solve", 0, 1)]),
                        ("solve",)) is None
    assert tr.summarize(tr.Trace(devices={"d": [("f", 0, 1)]}, host=[]),
                        ("solve",)) is None


def test_trace_without_a_tpu_plane_is_refused():
    with pytest.raises(ValueError, match="no /device:TPU"):
        tr.load(str(CPU_TRACE))


def test_recorded_cpu_trace():
    t = tr.load(str(CPU_TRACE), cpu_ok=True)
    assert list(t.devices) == ["/host:CPU"]
    assert sum(1 for n, _, _ in t.host if n == "solve") == 2
    s = tr.summarize(t, ("solve",))
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(
        tr.measure(tr.busy(t.devices["/host:CPU"],
                           *tr.span_extent(t.host, ("solve",)))) * 1e-9)
    assert s["reduction_exposed_s"] == 0 and s["halo_exposed_s"] == 0
    assert s["device_ops"] and all(v > 0 for _, v in s["device_ops"])
    assert sum(v for _, v in s["idle_gaps"]) <= s["window_s"] - s["busy_s"]
