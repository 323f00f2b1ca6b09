"""mythril_tpu — a TPU-native symbolic-execution security analyzer for EVM bytecode.

A ground-up JAX/XLA/Pallas re-design of the capabilities of terasum/mythril
(reference layout surveyed in SURVEY.md; mount was empty, citations ⚠unv):

- the per-opcode symbolic state transition of the reference's LASER engine
  (``mythril/laser/ethereum/svm.py`` ⚠unv) becomes a vmapped 256-bit
  (8 x u32 limb) interpreter over a struct-of-arrays frontier of
  (contract, path) lanes;
- path conditions live on an on-device SSA constraint tape decided by
  batched bit-vector constraint propagation with a massively parallel
  guided model search (the reference's Z3 ``Solver.check()`` in
  ``mythril/laser/smt`` ⚠unv has no Z3 available here — the solver stack
  is self-built and TPU-first);
- search strategies (``mythril/laser/ethereum/strategy`` ⚠unv) become
  frontier-scheduling policies over masked lanes;
- the SWC detection-module suite (``mythril/analysis/module`` ⚠unv)
  consumes *batched* states through a source-compatible API.

x64 mode is required for u64 limb intermediates and is enabled on import.
"""

import jax

jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
