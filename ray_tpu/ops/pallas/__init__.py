"""Pallas TPU kernels for the hot ops (flash attention & friends).

These kernels override the XLA-path reference implementations in
``ray_tpu/ops/`` on real TPUs; every kernel also runs in pallas interpret
mode so CPU CI exercises identical code.

The package imports the flash kernels alone. Every other kernel's module is
imported by the code that calls it, where it calls it: ``kda_insides``
(everything of a chunk of the chunked delta rule that does not read the
state, forward and backward: the gates' running sum, the two decayed
products, the inverse, ``W``, ``U`` and the reweighted operands; the walk
over the chunks and a chunk's outputs stay XLA) by ``ops/kda.py:_insides``
in the branch that a plan with ``impl`` ``"pallas_insides"`` takes, and
``kda_mix`` (a ``kda`` layer's elementwise chains round its recurrence) by
``models/mixers.kda_half`` where the plan's ``mix`` is ``"pallas"``, so that
a process whose steps hold no ``kda`` layer never loads or compiles either
(``tests/test_kimi_linear_training.py``).
"""

from ray_tpu.ops.pallas.flash import flash_attention, flash_attention_with_lse  # noqa: F401
