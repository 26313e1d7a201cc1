"""trace_reduce on a small trace recorded on a TPU v5e
(``make_trace_fixture.py``): three rounds of a jitted matmul chain, each
inside a ``bench.step_slot_chunked`` span and followed by a 4 ms host sleep
inside a ``bench.poll`` span, all inside ``bench.traced``."""
import benchpath
import pytest

import trace_reduce

FIXTURE = benchpath.FIXTURES / "step.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(FIXTURE)


def test_device_ops_and_spans_found(pd):
    ops = trace_reduce.device_ops(pd)
    assert len(ops) == 1
    (plane, events), = ops.items()
    assert plane.startswith("/device:TPU:0")
    assert all(e > s for s, e, _ in events)
    names = [n for _, _, n in trace_reduce.host_spans(pd)]
    assert names.count("bench.step_slot_chunked") == 3
    assert names.count("bench.poll") == 3 and names.count("bench.traced") == 1


def test_busy_idle_and_gaps(pd):
    r = trace_reduce.reduce(pd)
    win = [(s, e) for s, e, n in trace_reduce.host_spans(pd) if n == "bench.traced"][0]
    assert r["window_s"] == pytest.approx((win[1] - win[0]) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    # busy is the union of op intervals inside the window
    events = list(trace_reduce.device_ops(pd).values())[0]
    union = trace_reduce.merge([(max(s, win[0]), min(e, win[1]))
                                for s, e, _ in events if e > win[0] and s < win[1]])
    assert r["busy_s"] == pytest.approx(sum(e - s for s, e in union) * 1e-9)
    # the three host sleeps of 4 ms are idle and charged to bench.poll
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.poll"] >= 3 * 0.004 * 0.9
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    # the ops that took most time are the chain's eight matmul fusions,
    # under XLA's op names with their result types
    ops = r["device_ops"]
    assert sorted(n for n, _ in ops[:8]) == sorted(
        f"%convolution_tanh_fusion{s} bf16[2048,2048]"
        for s in ["", ".1", ".2", ".3", ".4", ".5", ".6", ".7"])
    assert ops == sorted(ops, key=lambda x: -x[1])
    assert sum(v for _, v in ops) <= r["busy_s"] * 1.0001


def test_merge_unions_overlaps():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_self_time_leaves_out_nested_ops():
    """A while loop listed with its body's ops keeps only its own time."""
    ops = [(0, 100, "%while.1"), (10, 40, "%fusion.1"), (50, 90, "%fusion.2"),
           (120, 130, "%copy.1")]
    own = {n: t for _, _, n, t in trace_reduce.self_times(ops)}
    assert own == {"%while.1": 30, "%fusion.1": 30, "%fusion.2": 40, "%copy.1": 10}
    assert trace_reduce.op_name(
        "%fusion.12 = bf16[16,2048]{1,0:T(8,128)(2,1)} fusion(bf16[16] %x)") == \
        "%fusion.12 bf16[16,2048]"
