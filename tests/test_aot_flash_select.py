"""The flash kernels under a learned choice and the indexer loss's target
(PR 65) at Keye-VL-2.0's cell's shape, compiled for one described chip.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows). The cell's
WHOLE step compiles the same way in a scratch script (the verify skill,
section 3: 42 s; peak 12.53 GiB of 15.75 at PR 63, six layers' choices kept
as int8 among it) and no test here holds it: tier-1 has under 80 s of its
1,470 left, and what interpret mode cannot show of this change is the
kernels' int8 tile, which this compile asks Mosaic about in a few seconds.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import sparse_index
from ray_tpu.ops.pallas import flash, index_target

from _aot import compiled_kernel, topo  # noqa: F401 (fixtures)


def test_the_kernels_under_a_choice_compile_for_v5e_at_keyes_shape(topo):
    """32 heads over 4 of 128, s 16,384, a choice [1, s, s] int8 for all
    heads: Mosaic takes an int8 tile of 1024 x 1024 beside K and V in all
    three kernels (its widening to 32 bits, the compare, the AND with the
    causal test; dkv's the turned choice), each call carries the name
    ``benchmark/kernels/flash_select.py`` costs it by, and the planned tile
    with the choice's 6 bytes an element stays inside the budget."""
    from benchmark.kernels import flash_select

    b, s, h, hkv, d, topk = 1, 16384, 32, 4, 128, 2048
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one)
    select = jax.ShapeDtypeStruct((b, s, s), jnp.int8, sharding=one)

    def loss(q, k, v, select):
        return flash.flash_attention(q, k, v, select=select, topk=topk
                                     ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, select).compile()
    customs = [line.strip() for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert sorted(map(flash_select.call_shape, customs)) == [
        (kind, b * h, s, s, d, True, topk, 2) for kind in ("dkv", "dq", "fwd")]
    for kind in flash.KINDS:
        p = flash.plan(s, s, d, 2, True, kind, select=True)
        assert p.vmem_bytes <= p.vmem_limit_bytes <= 64 << 20, p
        assert (p.block_q, p.block_k) == (1024, 1024), p
        assert p.live_steps == p.edge_steps == 136, p


@pytest.mark.parametrize("keys", [2048, 16384])
def test_the_indexer_losss_target_compiles_for_v5e_at_keyes_shape(topo, keys):
    """A block of 256 rows of 32 heads over 4 of 128 against the first and
    the last span's keys: Mosaic takes the queries as [4, 8 * 256, 128], the
    log-sum-exp as the column beside them, the choice's int8 tile of 256 x
    512 and a traced first row; the call carries the name the ledger's
    ``device_ops`` shows (no ``flash_`` in it: ``flash_select_roofline``
    reads the three attention calls alone), and the planned tile stays
    inside the budget."""
    b, rows, h, hkv, d = 1, 256, 32, 4, 128
    one = SingleDeviceSharding(topo.devices[0])
    tile = sparse_index.kernel_tile("flash", 16384, h, hkv, d, 2)
    assert tile == 512 == index_target.tile_keys(rows, keys, h, hkv, d, 2)
    need = index_target.vmem_bytes(rows, tile, h, hkv, d, 2)
    assert 16 << 20 < need <= flash._VMEM_BUDGET_BYTES == 40 << 20
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
              for shape, dtype in [((b, rows, h, d), jnp.bfloat16),
                                   ((b, hkv, keys, d), jnp.bfloat16),
                                   ((b, h, rows), jnp.float32),
                                   ((b, rows, keys), jnp.int8),
                                   ((), jnp.int32)]]
    compiled = jax.jit(functools.partial(
        index_target.index_target, scale=d ** -0.5, tile=tile)).lower(
        *shapes).compile()
    customs = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(customs) == 1
    name = f"index_target_bh{b * h}_r{rows}_k{keys}_d{d}_g{h // hkv}"
    assert name in customs[0] and "flash_" not in name
    assert f"f32[{b},{rows},{keys}]" in customs[0]
    # nothing [heads, rows, keys] round the call: the block's p and no more
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * keys
