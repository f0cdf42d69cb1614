"""The reduction on a small trace recorded on a v5e (three runs of a jitted
sort of 1M int32, 10 ms of sleep between them), and on intervals by hand."""

import pathlib

import reduce_trace

TRACE = pathlib.Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


def test_union_and_gaps():
    busy = reduce_trace.union([(5, 9), (0, 2), (1, 3), (9, 10)])
    assert busy == [(0, 3), (5, 10)]
    assert reduce_trace.gaps(busy, 0, 12) == [(3, 5), (10, 12)]


def test_label_gaps():
    ms = 1_000_000
    host = [(0, 100 * ms, "np.asarray(jax.Array)"),
            (100 * ms, 110 * ms, "PjitFunction(f)")]
    idle = reduce_trace.label_gaps(
        [(10 * ms, 90 * ms), (100 * ms, 200 * ms), (300 * ms, 300 * ms + 5)],
        host,
    )
    assert idle == {"np.asarray(jax.Array)": 80 * ms,
                    reduce_trace.UNTRACED: 100 * ms,
                    reduce_trace.BETWEEN_OPS: 5}


def test_recorded_trace():
    out = reduce_trace.reduce_planes(
        reduce_trace.read_planes(str(TRACE)), 0.5
    )
    assert out["devices"] == 1
    # three program runs of 1.14 ms each
    assert 3.3e-3 < out["busy_s"] < 3.6e-3
    # nearly all of it the sort; nested events are not counted twice
    assert 0.95 < out["sort_s"] / out["busy_s"] <= 1.0
    ops = dict(out["breakdown"]["device_ops"])
    assert "program jit__lambda" in ops
    assert any(name.startswith("%sort") for name in ops)
    idle = dict(out["breakdown"]["idle_gaps"])
    # two sleeps of 10 ms between the runs, with no host event in them
    assert 0.02 < idle[reduce_trace.UNTRACED] < 0.03


def test_no_device_plane_gives_nothing():
    out = reduce_trace.reduce_planes([("/host:CPU", [("python", [])])], 1.0)
    assert out["busy_s"] is None and out["breakdown"] is None
