"""The reduction from a profiler trace to busy time, top operations, idle
gaps by host span and kernel time, on a small trace recorded on a TPU v5e
(three launches of a jitted matmul + flash-attention step with a 20 ms
``bench:batch_fetch`` sleep between them; PR 22)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from benchmark.lib import arithmetic, trace  # noqa: E402

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(SMALL)


def test_busy_and_idle(reduced):
    assert reduced["devices"] == 1
    # three programs of ~0.3 ms in a window of ~66 ms
    assert 0.8e-3 < reduced["busy_s"] < 1.0e-3
    assert 0.06 < reduced["window_s"] < 0.07
    assert reduced["busy_s"] < reduced["window_s"]


def test_top_operations_are_named_as_the_trace_names_them(reduced):
    ops = dict(reduced["device_ops"])
    assert len(reduced["device_ops"]) <= 10
    assert reduced["device_ops"][0][0] == "%step.1"  # the Pallas call
    assert "%convolution_tanh_fusion" in ops
    assert sum(ops.values()) <= reduced["busy_s"] * 1.001


def test_gaps_go_to_the_host_span_that_covers_them(reduced):
    gaps = dict(reduced["idle_gaps"])
    # two whole sleeps lie between the three launches
    assert gaps["batch_fetch"] > 0.04
    assert abs(sum(gaps.values()) + reduced["busy_s"] - reduced["window_s"]) < 1e-4


def test_an_unattributed_gap_takes_the_default_label():
    reduced = trace.reduce_trace(SMALL, idle_label="engine-tick-unattributed")
    assert all(name in ("batch_fetch", "launch", "fence", "engine-tick-unattributed")
               for name, _ in reduced["idle_gaps"])


def test_flash_kernel_time_and_roofline(reduced):
    assert reduced["flash"]["calls"] == 3
    assert reduced["kernel_s"] == pytest.approx(reduced["flash"]["seconds"])
    # forward, 8 heads x 1024 x 128, causal: 2 products of 2*(s*s/2)*d each
    assert reduced["flash"]["flops"] == 3 * 2 * 8 * 1024 * 1024 * 128
    share = trace.flash_roofline(reduced, "TPU v5 lite")["share"]
    assert 0.01 < share < 0.2  # a tiny call: far from the peak, above zero
    with pytest.raises(KeyError):
        trace.flash_roofline(reduced, "TPU v9")  # no peak, no default


@pytest.mark.parametrize("result,kind", [
    ("(bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})", "fwd"),
    ("bf16[32,4096,128]{2,1,0}", "dq"),
    ("(bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0})", "dkv"),
])
def test_flash_calls_are_told_apart_by_their_results(result, kind):
    name = (f'%k.1 = {result} custom-call(bf16[32,4096,128]{{2,1,0}} %q), '
            f'custom_call_target="tpu_custom_call"')
    assert arithmetic.flash_call_kind(name) == (kind, 32, 4096, 128, 2)
    assert arithmetic.flash_call_kind(name.replace("tpu_custom_call", "x")) is None


@pytest.mark.parametrize("name,collective", [
    ("%all-gather-start.3 = (bf16[8]) all-gather-start(bf16[2] %p)", True),
    ("%all-reduce.1 = f32[] all-reduce(f32[] %x), to_apply=%add", True),
    ("%fusion.7 = bf16[8] fusion(bf16[8] %all-gather-done.3), kind=kLoop", False),
    ("%collective-permute-done = bf16[4] collective-permute-done(%s)", True),
])
def test_collectives_by_name(name, collective):
    assert trace.is_collective(name) is collective


def test_a_loop_does_not_count_its_body_twice():
    """``%while`` lies on the line over its body's operations (seen on the
    chip: the train step's whiles summed to more than the busy time)."""
    events = [(0.0, 10.0, "%while.1 = () while()"),
              (1.0, 4.0, "%fusion.1 = f32[] fusion()"),
              (4.0, 9.0, "%while.2 = () while()"),
              (5.0, 8.0, "%k = bf16[1,2,3] custom-call()"),
              (12.0, 13.0, "%copy.1 = f32[] copy()")]
    own = dict((trace.instruction(n), s) for n, s in trace._self_times(events))
    assert own == {"%while.1": 2.0, "%fusion.1": 3.0, "%while.2": 2.0,
                   "%k": 3.0, "%copy.1": 1.0}
    assert sum(own.values()) == 11.0  # the union of the intervals
