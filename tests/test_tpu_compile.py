"""Main-path kernels compile for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a device that is
described, not attached (``jax.experimental.topologies``): what it
refuses here, it refuses on the chip. Kept to the programs that compile
in seconds — the keccak and SHA-256 kernels at the width the campaign
runs them (DEFAULT_LIMITS, P = 32 x 32 lanes) and the host-callback
custom call the precompile dispatcher puts inside ``sym_run``. The
engine itself is too slow for tier-1: full-width ``sym_run`` (one
64-step chunk, callback branch taken) compiles for this device in
259 s, ``sym_superstep`` in 232 s and the concrete ``core.run`` in
101 s on this sandbox's CPU (CHANGES.md, PR 23); compile time follows
the program's size, not its shapes, so the test limits do not help.

Only one process at a time may load the TPU's library, and the
xdist workers each import this file: the topology is described inside a
module-scoped fixture, never at import, and every compile runs in the
test's own process. All of them stay in this one file.
"""

import os

import pytest

import mythril_tpu  # noqa: F401  (enables x64)

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mythril_tpu.config import DEFAULT_LIMITS

P = 32 * 32  # --batch-size 32 x --lanes-per-contract 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described device is written to the
    # persistent cache but cannot be read back without a chip: keep
    # the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled


def _hash_kernel(name):
    if name == "keccak":
        from mythril_tpu.ops.keccak import keccak256_device
        return keccak256_device, DEFAULT_LIMITS.max_hash_bytes
    from mythril_tpu.ops.sha256 import sha256_device
    from mythril_tpu.symbolic.engine import PRE_IN_CAP
    return sha256_device, min(DEFAULT_LIMITS.mem_bytes, PRE_IN_CAP)


@pytest.mark.parametrize("name", ["keccak", "sha256"])
def test_hash_kernel_compiles_for_v5e(one_chip, name):
    fn, width = _hash_kernel(name)
    compiled = _compile(
        fn,
        jax.ShapeDtypeStruct((P, width), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((P,), jnp.int32, sharding=one_chip))
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (P, 8) and out.dtype == jnp.uint32


def test_host_callback_lowers_for_v5e(one_chip):
    """The custom call ``_apply_precompiles`` embeds when the runtime
    supports host callbacks (ops/callbacks.py): per-lane bytes out to
    the host natives and back, as the TPU lowers it."""
    def natives(inp):
        return jax.pure_callback(
            lambda a: a[:, :64],
            jax.ShapeDtypeStruct((P, 64), jnp.uint8), inp)

    compiled = _compile(
        natives, jax.ShapeDtypeStruct((P, 448), jnp.uint8,
                                      sharding=one_chip))
    assert "host" in compiled.as_text().lower()
