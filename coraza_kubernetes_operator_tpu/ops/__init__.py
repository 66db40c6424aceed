"""JAX/Pallas device kernels for the TPU data plane.

- ``transforms``: vectorized byte-level Seclang transformations over
  ``[batch, len]`` uint8 tensors.
- ``segment``: the conv tier, every position of a segment plan in one MXU
  convolution and the chains over its match bitmap.
- ``dfa_flat``: the dense-DFA blocks (exact nfa buckets, dfa-hot blocks,
  prefilter approximations) as fused flat-slot bins, one Pallas kernel a
  bin on a TPU (docs/AUTOMATA.md).
- ``dfa``: the plain bank scan, a ``lax.scan`` over stacked DFA tables:
  a block no bin holds, the conv tier's long banks, the bins' oracle.
- ``dfa_host``: the same automata in NumPy, for the host fallback.

All kernels are shape-static and jit-safe: control flow is ``lax.scan``/
``jnp.where`` only, per the XLA compilation model.
"""

from .dfa import DFABank, scan_dfa_bank, stack_dfas  # noqa: F401
