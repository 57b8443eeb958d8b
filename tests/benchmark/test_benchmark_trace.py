"""The reduction from a profiler trace to busy time, top operations, device
time by program and scope, idle gaps by host span and kernel time, on a
small trace recorded on a TPU v5e (three launches of a jitted matmul +
flash-attention step with a 20 ms ``bench:batch_fetch`` sleep between them;
PR 22)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

REPO = lib.REPO
sys.path.insert(0, REPO)
from benchmark.lib import spec, trace  # noqa: E402

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
    flash = reduced["kernels"]["flash"]
    assert flash["calls"] == 3
    assert reduced["kernel_s"] == pytest.approx(flash["seconds"])
    # forward, 8 heads x 1024 x 128, causal: 2 products of 2*(s*s/2)*d each
    assert flash["flops"] == 3 * 2 * 8 * 1024 * 1024 * 128
    share = trace.kernel_roofline(reduced, "flash", "TPU v5 lite")["share"]
    assert 0.01 < share < 0.2  # a tiny call: far from the peak, above zero
    with pytest.raises(KeyError):
        trace.kernel_roofline(reduced, "flash", "TPU v9")  # no peak, no default
    assert trace.kernel_roofline(reduced, "absent", "TPU v5 lite") is None


def test_the_reduction_gives_what_it_gave(reduced):
    """What the parent commit's ``reduce_trace`` said of this file (PR 24),
    to the bit: the code moved, the numbers did not."""
    assert reduced["devices"] == 1
    assert reduced["window_s"] == 0.06584330000000001
    assert reduced["busy_s"] == 0.0009050139999999818
    assert reduced["kernel_s"] == 0.00082916299999998
    assert reduced["collective_s"] == 0.0
    assert reduced["device_ops"] == [
        ["%step.1", 0.00082916299999998],
        ["%convolution_tanh_fusion", 3.7874999999992776e-05],
        ["%copy.3", 1.9882000000005784e-05],
        ["%copy-done.1", 7.216000000011269e-06],
        ["%copy.1", 4.052000000011324e-06],
        ["%copy_bitcast_fusion", 2.057999999999227e-06],
        ["%broadcast.4", 2.05600000000028e-06],
        ["%broadcast.2", 2.049999999996499e-06],
        ["%copy.2", 5.270000000071051e-07],
        ["%copy-start", 3.999999999976245e-08]]
    assert reduced["idle_gaps"] == [["batch_fetch", 0.06493820200000001]]
    assert reduced["kernels"]["flash"] == {
        "seconds": 0.00082916299999998, "flops": 6442450944.0,
        "bytes": 25165824.0, "calls": 3.0}  # the parent's ``flash``


def test_device_time_by_program_and_scope(reduced):
    """Every operation's own time under ``<program>/<scope>``: all of it, so
    the sum is the busy time. This trace predates the program's scopes: its
    one program is ``jit_step`` and nothing in it is named."""
    import jax

    data = jax.profiler.ProfileData.from_file(SMALL)
    own = sum(s for plane in data.planes for line in plane.lines
              if plane.name.startswith("/device:TPU") and line.name == "XLA Ops"
              for _, s in trace._self_times(trace._events(line)))
    assert sum(reduced["by_scope"].values()) == pytest.approx(own, rel=1e-12)
    assert own == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert set(reduced["by_scope"]) == {"jit_step/other"}
    ops = trace._op_metadata(SMALL)[0]
    program, tf_op = ops[next(n for n in ops if n.startswith("%broadcast.4 = "))]
    assert tf_op == "jit(step)/broadcast_in_dim:" and program == 12738778776591204272


@pytest.mark.parametrize("tf_op,scope", [
    # as the cells' traces on the chip have them (PR 25)
    ("jit(rt_decode)/while/body/closed_call/while/body/closed_call/mlp/dot_general:", "mlp"),
    ("jit(rt_decode)/while/body/closed_call/while/body/closed_call/attn/bqhgd,bkhd->bhgqk/dot_general:", "attn"),
    ("jit(rt_decode)/while/body/closed_call/head_sample/argmax:", "head_sample"),
    ("jit(rt_prefill)/kv_scatter/dynamic_update_slice:", "kv_scatter"),
    ("jit(rt_decode)/while/body/dynamic_slice:", "other"),
    ("jit(steps)/while/body/closed_call/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/flash_fwd/pallas_call:", "flash_fwd"),
    ("jit(steps)/while/body/closed_call/transpose(jvp())/while/body/closed_call/"
     "checkpoint/jit(log_softmax)/reduce_max:", "other"),
    ("jit(steps)/jit(main)/transpose(jvp(moe_experts))/dot_general:", "moe_experts"),
    # the sharded Mixtral step, as its compiled program names them
    ("jit(steps)/while/body/closed_call/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe_router/jit(cumsum)/_moe_ffn/reduce_window_sum", "moe_router"),
    ("jit(steps)/while/body/closed_call/jvp()/while/body/closed_call/"
     "bqhgd,bkhd->bhgqk/dot_general", "other"),
    ("jit(steps)/while/body/closed_call/mul;while/body/closed_call", "other"),
    ("jit(step)/broadcast_in_dim:", "other"),
    ("", "other"),
])
def test_scope_is_the_outermost_name_the_program_gave(tf_op, scope):
    assert trace.scope_of(tf_op) == scope


def test_a_collective_has_a_line_of_its_own_under_its_scope():
    grad = "jit(steps)/while/body/closed_call/transpose(jvp(moe_experts))/dot_general"
    assert trace.scope_key("jit_steps", grad, "%fusion.9 = bf16[8] fusion(bf16[8] %p)") \
        == "jit_steps/moe_experts"
    assert trace.scope_key("jit_steps", grad, "%all-reduce.122 = bf16[8] all-reduce(%x)") \
        == "jit_steps/moe_experts/collective"
    assert trace.scope_key("unnamed", "", "%all-gather-start.3 = (bf16[8]) "
                           "all-gather-start(bf16[2] %p)") == "unnamed/other/collective"


def test_a_kernel_is_a_file(tmp_path, reduced):
    """A file added to ``benchmark/kernels/`` of a copy is costed beside the
    flash kernels, and nothing else of the reduction changes."""
    root = lib.make_copy(str(tmp_path))
    again = trace.reduce_trace(SMALL, root=root)
    matmul = again["kernels"].pop("tiny-matmul")
    assert again == reduced
    assert matmul["calls"] == 3 and matmul["flops"] == 3 * 2 * 1024 ** 3
    assert matmul["seconds"] == dict(reduced["device_ops"])["%convolution_tanh_fusion"]
    share = trace.kernel_roofline(again | {"kernels": {"tiny-matmul": matmul}},
                                  "tiny-matmul", "TPU v5 lite")
    assert share["compute_bound"] and 0.5 < share["share"] < 1.0


@pytest.mark.parametrize("result,kind", [
    ("(bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})", "fwd"),
    ("bf16[32,4096,128]{2,1,0}", "dq"),
    ("(bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0})", "dkv"),
])
def test_flash_calls_are_told_apart_by_their_results(result, kind):
    name = (f'%k.1 = {result} custom-call(bf16[32,4096,128]{{2,1,0}} %q), '
            f'custom_call_target="tpu_custom_call"')
    flash = spec.load_kernels()["flash"]
    assert flash.call_kind(name) == (kind, 32, 4096, 128, 2)
    assert flash.match(name) == flash.call_cost(kind, 32, 4096, 128, 2)
    assert flash.match(name.replace("tpu_custom_call", "x")) is None


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
