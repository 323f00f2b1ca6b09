"""Host-callback capability probe.

Whether jitted ``jax.pure_callback`` works is a property of the runtime,
not of the platform: ``jax.default_backend()`` says "tpu" either way.
The only robust detection is empirical: compile and run a trivial
callback once per process and cache the verdict.

Callers (the precompile dispatcher) choose at TRACE TIME between the
host-callback path and the sound uninterpreted-leaf fallback, so an
unsupported runtime costs precision (concrete ecrecover/bn128/blake2f
degrade to havoc leaves), never correctness.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger(__name__)

_CB_OK: Optional[bool] = None


def host_callbacks_supported() -> bool:
    """True iff jitted ``pure_callback`` works on the default backend.

    Resolved on first use, which is usually while ``sym_run`` is being
    traced. ``ensure_compile_time_eval`` runs the probe eagerly there:
    its callback executes on the device now and leaves no equation in
    the outer program."""
    global _CB_OK
    if _CB_OK is None:
        forced = os.environ.get("MYTHRIL_HOST_CALLBACKS")
        if forced is not None:
            _CB_OK = forced not in ("0", "off", "no")
            return _CB_OK
        import jax
        import jax.numpy as jnp

        try:
            with jax.ensure_compile_time_eval():
                out = jax.jit(
                    lambda x: jax.pure_callback(
                        lambda a: a,
                        jax.ShapeDtypeStruct((), jnp.int32),
                        x,
                    )
                )(jnp.int32(7))
                _CB_OK = int(out) == 7
        except Exception as e:  # noqa: BLE001 — any failure means "no"
            log.warning("host callbacks unavailable on %s, precompile "
                        "natives degrade to havoc leaves: %r",
                        jax.default_backend(), e)
            _CB_OK = False
    return _CB_OK
