"""What the files that ask the chip's compiler share (``test_aot_*.py``):
the described topology, the compile of a cell's fused step, and the readings
of a compiled step that more than one cell's file holds its own step to.
Imported, not collected; a file takes the two fixtures by name
(``from _aot import compiled_kernel, topo``).

A whole step's compile is its file's one module fixture, named as an
argument by every test that reads it: it is built in the set-up of the
file's first test, outside the 300 s a test's call may take, and a file of
two to five tests lies in one of ``xdist``'s chunks, so it is built once.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.pallas import flash
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.context import mesh_scope
from ray_tpu.parallel.plan import compile_plan
from ray_tpu.util import hlo_copies

CFG_1B = llama.PRESETS["1b"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def compiled_kernel(monkeypatch):
    """The process's backend is the CPU, where ``flash`` picks interpret
    mode; these tests are about the Mosaic kernel."""
    monkeypatch.setattr(flash, "_needs_interpret", lambda: False)


def _on(sharding, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _as_sharded(tree, shardings):
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, shardings)


def _compile_fused_step(fam, cfg, mesh, k, batch, seq):
    """The fused-K train step of ``cfg`` compiled for ``mesh``'s described
    chips: (plan, parameter shardings, compiled). Module fixtures are set up
    before the function-scoped ``compiled_kernel``, so the Mosaic kernel is
    asked for here too."""
    opt = ts.default_optimizer(total_steps=100)
    plan = compile_plan(cfg, mesh)
    p_sh, o_sh = plan.state_shardings(opt)
    p_abs = jax.eval_shape(lambda: fam.init_params(jax.random.key(0), cfg))
    o_abs = jax.eval_shape(opt.init, p_abs)
    tokens = {"tokens": jax.ShapeDtypeStruct(
        (k, batch, seq + 1), jnp.int32,
        sharding=plan.batch_sharding(3, False, True))}
    multi = ts.make_multi_step(cfg, opt, k, mesh=mesh, plan=plan)
    with pytest.MonkeyPatch.context() as mp, mesh_scope(mesh):
        mp.setattr(flash, "_needs_interpret", lambda: False)
        return plan, p_sh, multi._jit.lower(
            _as_sharded(p_abs, p_sh), _as_sharded(o_abs, o_sh),
            tokens).compile()


def _head_collectives(compiled, dims):
    """The collectives of a compiled step whose result holds the loss's
    head at ``dims``, one per channel: where the TPU compiler makes a
    collective asynchronous, its start, continuation and done fusions each
    spell the instruction out under the one ``channel_id``, and
    ``hlo_copies.collectives`` lists all three."""
    text = compiled.as_text()
    by_channel = {}
    for c in hlo_copies.collectives(compiled):
        if any(d == dims for _, d in c["arrays"]):
            channel = re.search(
                rf"%{re.escape(c['name'])} = .*?channel_id=(\d+)", text)
            by_channel.setdefault(channel.group(1) if channel else c["name"],
                                  c)
    return list(by_channel.values())


def holds_the_head_once_a_step(step, compiled, cfg, k, seq, tp, capsys):
    """``chunked_ce``'s loop closes over a head whole along d
    (``llama.head_for_loss_loop``): the head is gathered once a step before
    the loop and its gradient summed over the chips once after it, V left
    on ``tp``. At most 3 collectives a step hold the head and none runs per
    chunk. Fails on the parent of the change that brought it: there the
    Mixtral step gathers ``[4096, 32000]`` 16 times forward and 16 times in
    the rematted backward a step (64 runs a launch of 2, and 32 more of
    the gradient's ``[1024, 32000]`` reduce-scatter); the ``fsdp 2 x tp 2``
    one gathers ``[2048, 16000]`` 8 + 8 times a step (32 a launch)."""
    chunks = seq // cfg.loss_chunk
    found = _head_collectives(compiled, (cfg.d_model, cfg.vocab_size // tp))
    with capsys.disabled():
        print(f"\n{step}: collectives that hold the head, a launch of {k}:")
        for c in found:
            print(f"  {c['kind']} {c['arrays']} x{c['runs']} {c['op_name']}")
    assert found and {c["kind"] for c in found} >= {"all-gather"}, found
    assert sum(c["runs"] for c in found) <= 3 * k, found
    for c in found:
        assert c["runs"] % (chunks * k), c
    # nor does anything else as wide as the vocabulary cross chips per chunk
    # (the parent's gradient, reduce-scattered as [d / fsdp, V], and the
    # chunk's [b, 256, V] logits' cotangent, gathered for it)
    # nor once a group: the loop's chunks are unrolled inside a group, so a
    # collective of theirs would run ``groups * k`` times (the ``fsdp 4``
    # step did, 32 gathers of a chunk's cotangent over the batch and the
    # head's once a group, until the head was placed inside the loop's body)
    for c in hlo_copies.collectives(compiled):
        for _, dims in c["arrays"]:
            if len(dims) > 1 and dims[-1] == cfg.vocab_size // tp:
                assert c["runs"] % (chunks * k) and c["runs"] <= k, c


def runs_no_second_forward(step, compiled, layer_kinds, temp, peak, capsys):
    """A layer's remat block keeps the flash forward's output and
    log-sum-exp (``llama.remat_block``), so the compiled train step holds as
    many ``flash_fwd`` calls as ``flash_dq`` calls, through the dense scan
    under ``shard_map`` on four chips, Mixtral's scan and the patterned
    walk's banded and full layers alike. Fails on the parent, whose backward
    ran the forward kernel again for them (2 : 1). What the kept arrays
    cost is printed beside the parent's (``temp`` and ``peak``: the
    temporaries and peak in bytes of the same compile at commit 7246729,
    whose remat blocks kept the products' results alone)."""
    calls = [re.search(r"flash_(fwd|dq|dkv)", line).group(1)
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sorted(calls) == sorted(flash.KINDS * layer_kinds), calls
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{step}: temporaries {mem.temp_size_in_bytes / 2**20:.1f} "
              f"MiB (parent {temp / 2**20:.1f}), peak "
              f"{mem.peak_memory_in_bytes / 2**20:.1f} MiB (parent "
              f"{peak / 2**20:.1f})")


# ---- the chunked loss's loop: three vocabulary-wide products a chunk ----------

def _loss_loop(compiled, scope, cfg, tp=1):
    """What the compiled step runs under ``scope`` of ``llama._looped_ce``'s
    loop, in one launch: (runs of products that read or write
    an array as wide as the vocabulary a chip holds, runs of instructions
    that write a ``[d, V]`` array), each as (name, runs) rows. A product is a
    ``convolution`` inside or outside a fusion; its operands' shapes are its
    computation's parameters'."""
    from benchmark.lib.trace import scope_of

    wide = cfg.vocab_size * max(getattr(cfg, "n_pred_heads", 1), 1) // tp
    module = hlo_copies._Module(compiled.as_text())
    products, writes = [], []
    for times, (name, shape, opcode, operands, line), comp in module.walk(
            fusions=True):
        if scope_of(_op_name(line)) != scope:
            continue
        if opcode == "convolution":
            shapes = [shape] + [i[1] for i in comp if i[0] in operands]
            # (n heads' logits may stand as [chunk, n, V]: a run of
            # dimensions that multiplies to the width)
            if any(math.prod(dims[i:j]) == wide for text in shapes
                   for _, dims in hlo_copies._arrays(text)
                   for i in range(len(dims))
                   for j in range(i + 1, len(dims) + 1)):
                products.append((name, times))
    for times, (name, shape, opcode, _, line), _ in module.walk():
        if (opcode in ("fusion", "convolution", "copy")
                and scope_of(_op_name(line)) == scope
                and any(dims[-2:] == (cfg.d_model, wide)
                        for _, dims in hlo_copies._arrays(shape))):
            writes.append((name, times))
    return products, writes


def _assert_three_products_a_chunk(compiled, scope, k, cfg, seq, tp=1):
    """Under ``scope`` the step's loss runs, a step, two vocabulary-wide
    products a chunk of ``loss_chunk`` positions (the logits, the hidden's
    gradient) and one a GROUP of chunks (the head's gradient), and writes a
    ``[d, V]`` array once a group and not once a chunk. Fails on the
    parent, whose rematted loop ran four a chunk (the logits twice) and
    read and wrote the head's whole cotangent in each (Mistral's shape:
    ``convolution_add_fusion.5``, 64 runs a launch of 4)."""
    chunks = seq // cfg.loss_chunk
    groups = chunks // llama._chunks_a_group(chunks, cfg.loss_chunk)
    products, writes = _loss_loop(compiled, scope, cfg, tp)
    assert sum(runs for _, runs in products) == k * (2 * chunks + groups), (
        scope, products)
    # the groups' sums, and what a step makes of them once: the zero they
    # start from, the incoming cotangent's scale, a cast
    assert k * groups <= sum(runs for _, runs in writes) \
        <= k * (groups + 3), (scope, writes)
    return chunks, groups


# ---- every operation of a compiled train step under a scope of the program's ---
_TIMED = {"fusion", "convolution", "custom-call", "all-gather", "all-reduce",
          "reduce-scatter", "all-to-all", "collective-permute"}
# a scan's stacking of its per-layer results and slicing of its operands (and
# the patterned walk's picking of a layer out of its period's stack): JAX
# writes them, directly under the loop's body, and no scope can stand there
# (and an index the compiler folded out of such a slice, an ``s32[2]`` that
# carries the enclosing call's name and nothing after it: the widened
# stream's step has fourteen, two microseconds each)
_STACKING = re.compile(r"(/(body|closed_call)/(dynamic_update_slice"
                       r"|dynamic_slice|squeeze|slice|broadcast_in_dim)"
                       r"|/while|/closed_call)$")
_OP_NAME = re.compile(r'op_name="(jit\([^"]*)"')


def _op_name(line):
    """An instruction's ``op_name`` where it is a path of the program's (the
    compiler's own carry none, or a bare word: ``reduce_window_sum``)."""
    found = _OP_NAME.search(line)
    return found.group(1) if found else ""


def _scopes_of_a_step(compiled):
    """(instructions by scope, those of the scans' stacking, those the
    compiler rooted in an instruction of its own, the strays) of a compiled
    step's fusions, products, kernels and collectives outside fused
    computations, each stray and exception as (name, shape, op_name)."""
    from benchmark.lib.trace import scope_of

    module = hlo_copies._Module(compiled.as_text())
    named, stacking, rootless, strays = {}, [], [], []
    seen = set()
    for _, (name, shape, opcode, _, line), _ in module.walk():
        if opcode.replace("-start", "") not in _TIMED or name in seen:
            continue
        seen.add(name)
        if opcode == "custom-call" and "tpu_custom_call" not in line:
            continue  # AllocateBuffer, ConcatBitcast: the compiler's buffers
        op_name = _op_name(line)
        row = (name, shape.split("{")[0][:48], op_name)
        scope = scope_of(op_name)
        if not op_name:
            # the instruction is the compiler's: a fused computation under
            # it is judged by the named operations it holds
            called = re.search(r"calls=%?([\w.\-]+)", line)
            body = module.computations.get(called.group(1), []) if called else []
            inside = {scope_of(_op_name(inst[4])) for inst in body
                      if not _STACKING.search(_op_name(inst[4]))}
            rootless.append(row + (sorted(inside - {"other"}),))
        elif _STACKING.search(op_name):
            stacking.append(row)
        elif scope == "other":
            strays.append(row)
        else:
            named[scope] = named.get(scope, 0) + 1
    return named, stacking, rootless, strays


def names_all_of_itself(step, compiled, scopes, most_stacking, most_rootless,
                        cfg, k, seq, tp, capsys):
    """Every fusion, product, kernel and collective of the compiled step
    carries an ``op_name`` whose outermost scope, as the benchmark's
    reduction reads it (``benchmark/lib/trace.py:scope_of``), is one of
    ``train_step.STEP_SCOPES``: the old dense stack, the old MoE stack, the
    patterned walk and the EVA block alike, with the embedding, the loss and
    the optimizer's update. Fails on the parent, whose old stacks named
    nothing (``other`` was 89-91% of Mistral's step). What cannot be named
    is printed with its shape and held to a count: the scans' own stacking
    and slicing, and instructions the compiler rooted in one of its own (a
    ``bitcast`` after the last named operation, an expanded ``cumsum``, an
    async collective), whose fused computations hold named operations only.

    The loss is ``llama._looped_ce``'s rule, forward and backward under
    ``loss_head`` with nothing of it astray, and the compiled step holds
    what the rule says (``_assert_three_products_a_chunk``). ``scopes``: the
    scopes the step's operations must be found under; ``most_stacking``,
    ``most_rootless``: the most instructions that may carry none: the scans'
    and the walk's own stacking and slicing, and fusions or collectives the
    compiler rooted in an instruction of its own; since PR 60 among them the
    loss's unrolled chunks' writes of their cotangent and of their slice of
    ``dx`` into the group's stacks, sixteen in a group of eight, which hold
    ``loss_head`` only. ``cfg``, ``seq``, ``tp``: the config whose loss the
    step runs, positions a sequence, ways ``tp`` splits the vocabulary."""
    named, stacking, rootless, strays = _scopes_of_a_step(compiled)
    with capsys.disabled():
        print(f"\n{step}: {sum(named.values())} instructions under "
              + ", ".join(f"{k} {v}" for k, v in sorted(named.items()))
              + f"; {len(stacking)} of the scans' stacking, "
              f"{len(rootless)} rooted by the compiler")
        print("  stacking: " + "; ".join(
            f"{name} {shape} {op_name.rsplit('/', 1)[-1]}"
            for name, shape, op_name in stacking))
        print("  rooted by the compiler: " + "; ".join(
            f"{name} {shape} holds {','.join(inside) or '-'}"
            for name, shape, _, inside in rootless))
    assert not strays, strays
    assert set(named) == scopes <= set(ts.STEP_SCOPES), set(named) ^ scopes
    assert all(set(inside) <= set(ts.STEP_SCOPES)
               for *_, inside in rootless), rootless
    assert len(stacking) <= most_stacking and len(rootless) <= most_rootless
    chunks, groups = _assert_three_products_a_chunk(
        compiled, "loss_head", k, cfg, seq, tp)
    with capsys.disabled():
        print(f"  loss_head: {chunks} chunks in {groups} group(s) a step, "
              f"{2 * chunks + groups} vocabulary-wide products")
