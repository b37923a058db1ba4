"""The trace reduction on hand-built events, and the loader on a trace
recorded here on the CPU."""
import pytest

from chipbench import trace as T

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(name, start, end, plane=DEV, line=T.OPS_LINE):
    return T.Event(plane, line, name, float(start), float(end))


def fixture():
    """A 100 us traced window: device ops busy 0-20, 30-50 (two
    overlapping kernels), 80-90; host frames: the loop in _round during
    the first gap and in _poll > asarray during the second."""
    return [
        ev(T.OPEN, 0, 1, HOST, "python"),
        ev(T.CLOSE, 100_000, 100_001, HOST, "python"),
        ev("$loop.py:10 serve_stream", 0, 100_000, HOST, "python"),
        ev("$engine.py:777 _round", 19_000, 31_000, HOST, "python"),
        ev("$engine.py:800 _poll", 50_000, 85_000, HOST, "python"),
        ev("$array.py:1 asarray", 52_000, 79_000, HOST, "python"),
        ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 20_000),
        ev("%while.2 = (s32[]) while(s32[] %c)", 30_000, 50_000),
        ev("%flash_attention.3 = bf16[4] custom-call(bf16[4] %q)",
           30_000, 38_000),                       # inside the loop
        ev("%round_fused.7 = f32[4] custom-call(f32[4] %u)", 40_000, 50_000),
        ev("%fusion.4 = f32[8] fusion(f32[8] %p)", 80_000, 90_000),
        ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 95_000, 120_000),
        ev("fusion.1", 0, 100_000, line="XLA Modules"),   # not an op line
    ]


def test_busy_idle_kernels_and_gaps():
    r = T.reduce_events(fixture())
    assert r.window_s == pytest.approx(100e-6)
    # union: 0-20, 30-50, 80-90, 95-100 (clipped at the close)
    assert r.busy_s == pytest.approx(55e-6)
    assert r.idle_share == pytest.approx(0.45)
    assert r.kernel("flash_attention") == (pytest.approx(8e-6), 1)
    assert r.kernel("round_fused") == (pytest.approx(10e-6), 1)
    # the loop's own time is what its body leaves: 20 - 8 - 10
    assert r.kernel("while") == (pytest.approx(2e-6), 1)
    assert r.kernel("fusion")[1] == 3
    labels = dict((round(s * 1e6), lab) for lab, s in r.gaps)
    assert labels == {10: "_round", 30: "_poll > asarray", 5: "serve_stream"}
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    assert b["idle_gaps"][0] == ["_poll > asarray (x1)", pytest.approx(30e-6)]


def test_two_devices_average_busy():
    evs = fixture() + [ev("%fusion.2 = f32[8] fusion()", 0, 100_000,
                          plane="/device:TPU:1")]
    r = T.reduce_events(evs)
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx((55e-6 + 100e-6) / 2)
    assert r.kernel("flash_attention") == (pytest.approx(4e-6), 0)


def test_op_names():
    assert T.op_name("%flash_attention.5 = bf16[64,12] custom-call(s32[1] "
                     "%get-tuple-element.330)") == "flash_attention"
    assert T.op_name("%while.2 = (s32[]{:T(128)}, bf16[64]) while()") == \
        "while"
    assert T.op_name("copy-start.3") == "copy-start"
    assert T.op_name("fusion") == "fusion"


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events([e for e in fixture() if e.plane != DEV])


def test_loader_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.OPEN):
        pass
    jax.jit(lambda x: (x @ x).sum())(jnp.ones((32, 32))).block_until_ready()
    with jax.profiler.TraceAnnotation(T.CLOSE):
        pass
    jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    events = T.load_events(path)
    marks = [e for e in events if e.name in (T.OPEN, T.CLOSE)]
    assert [e.name for e in sorted(marks, key=lambda e: e.start)] == \
        [T.OPEN, T.CLOSE]
    assert len(T.host_line(events)) >= 2
