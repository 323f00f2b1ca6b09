#!/usr/bin/env python
"""Is the compiled engine still the same program?

Lowers (never compiles, never runs) ``sym_run`` with exactly the
arguments ``SymExecWrapper.explore`` passes in a benchmark cell, 8
contracts x 128 lanes at ``DEFAULT_LIMITS``, once over a corpus of
pairs and once over a deploying one (16 images, ``--concrete-storage``),
and prints the SHA-256 of each StableHLO text; ``bare`` is the pair
corpus without spill (``defer_starved=False``: one whole call, the
program in which no lane parks and no rule of the lane pool is
compiled). Two checkouts whose digests agree hand XLA the same module,
so they share one executable in the compile cache. A PR that says it
left the engine alone shows it by running this on its parent and on
itself:

    python tools/sym_run_digest.py                # this checkout
    python tools/sym_run_digest.py ../parent DIR  # another one; texts kept in DIR

On the CPU the TPU's dense slot writes are traced (``_use_scatter``
patched, as tests/test_write_paths.py does); on a chip nothing is.
One JSON object on stdout.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Lowered(Exception):
    pass


def creation_of(runtime: bytes) -> bytes:
    """solc's deploy epilogue around ``runtime``: PUSH2 len, DUP1,
    PUSH2 offset, PUSH1 0, CODECOPY, PUSH1 0, RETURN."""
    n, off = len(runtime), 13
    head = bytes([0x61, n >> 8, n & 255, 0x80, 0x61, off >> 8, off & 255,
                  0x60, 0x00, 0x39, 0x60, 0x00, 0xF3])
    assert len(head) == off
    return head + runtime


def main() -> int:
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else HERE)
    keep = sys.argv[2] if len(sys.argv) > 2 else None
    sys.path.insert(0, root)

    import jax

    import mythril_tpu.analysis.symbolic as asym
    from mythril_tpu.analysis import SymExecWrapper
    from mythril_tpu.core import interpreter as ci
    from mythril_tpu.disassembler.asm import erc20_like
    from mythril_tpu.symbolic import SymSpec

    assert os.path.abspath(asym.__file__).startswith(root + os.sep), (
        f"imported {asym.__file__}, not {root}")
    if jax.default_backend() == "cpu":
        ci._use_scatter = lambda: False  # what the TPU traces

    def lower_only(*a, **kw):
        raise _Lowered(jitted.lower(*a, **kw).as_text(), kw)

    jitted, asym.sym_run = asym.sym_run, lower_only
    codes = [erc20_like()] * 8
    out = {"root": root, "backend": jax.default_backend()}
    for cell, kw in (
            ("pairs", dict(spec=SymSpec())),
            ("bare", dict(spec=SymSpec(), spill=False)),
            ("deploys", dict(spec=SymSpec(storage=False),
                             creation_bytecodes=[creation_of(c)
                                                 for c in codes]))):
        try:
            SymExecWrapper(codes, lanes_per_contract=128, max_steps=256,
                           transaction_count=2, **kw)
        except _Lowered as e:
            text, call_kw = e.args
        else:
            raise SystemExit("explore() never called sym_run")
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, cell + ".mlir"), "w") as fh:
                fh.write(text)
        out[cell] = {"sha256": hashlib.sha256(text.encode()).hexdigest(),
                     "lines": text.count("\n"),
                     "static": {k: repr(v) for k, v in sorted(
                         call_kw.items())}}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
