"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package contains:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, layout, dtype policy)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

``INTERPRET`` is resolved at import from the active jax backend: compiled
Pallas (Mosaic) on a TPU, interpret mode everywhere else, which is how the
CPU test suite checks the kernels against ``ref.py``.  ``seafl_agg`` is
the server hot path and runs compiled on the chip (``chip_smoke.py``;
``tests/test_tpu_compile.py`` compiles it for a described v5e).  The
models in ``models/`` call no kernel yet: their attention, recurrence and
scan run as XLA.  Per-entry-point ``block_p`` and oracle routing come from
``runtime/autotune.py``.
"""

from repro.runtime.autotune import resolve_interpret

INTERPRET = resolve_interpret()
