"""Execution deadline + frontier checkpoint/resume.

Reference: ``--execution-timeout`` degrade semantics (SURVEY §5.3);
checkpointing is ABSENT in the reference — SURVEY §5.4 requires it here
for pod runs.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run
from mythril_tpu.utils.checkpoint import (CheckpointCorrupt,
                                          load_frontier,
                                          load_frontier_resilient,
                                          load_json_checkpoint,
                                          load_json_checkpoint_resilient,
                                          save_frontier,
                                          save_json_checkpoint)
from mythril_tpu.analysis import SymExecWrapper, fire_lasers

L = TEST_LIMITS
# loop bounding off so a spinner really spins (deadline must catch it)
L_NOLB = dataclasses.replace(TEST_LIMITS, loop_bound=0)

SPINNER = assemble(("label", "top"), ("ref", "top"), "JUMP")
BRANCHY = assemble(
    0, "CALLDATALOAD", ("ref", "a"), "JUMPI",
    1, 0, "SSTORE",
    4, "CALLDATALOAD", ("ref", "b"), "JUMPI",
    2, 1, "SSTORE", "STOP",
    ("label", "a"), 3, 0, "SSTORE", "STOP",
    ("label", "b"), 4, 1, "SSTORE", "STOP",
)


def test_deadline_aborts_spinner_with_partial_coverage():
    sym = SymExecWrapper(
        [SPINNER], limits=L_NOLB, lanes_per_contract=4,
        max_steps=1_000_000, transaction_count=2,
        execution_timeout=0.0, deadline_chunk_steps=8,
    )
    assert sym.timed_out
    assert len(sym.tx_contexts) == 1, "deadline stops further transactions"
    cov = sym.coverage
    assert cov.get("deadline_expired_running", 0) >= 1
    report = fire_lasers(sym)
    assert any("execution timeout" in w for w in report.coverage_warnings())


def test_deadline_not_hit_reports_clean():
    sym = SymExecWrapper(
        [assemble("STOP")], limits=L, lanes_per_contract=4,
        max_steps=64, transaction_count=1, execution_timeout=300.0,
    )
    assert not sym.timed_out
    assert "deadline_expired_running" not in sym.coverage


def _build(P=8):
    img = ContractImage.from_bytecode(BRANCHY, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(P, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(P, L, active=active)
    env = make_env(P)
    return sf, env, corpus


def _equal_trees(a, b) -> bool:
    import jax

    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(leaves_a, leaves_b))


def test_checkpoint_roundtrip_and_resume(tmp_path):
    sf, env, corpus = _build()
    spec = SymSpec()

    # uninterrupted reference run (64+64 segments use the same compiled
    # executable as the reference's shape family)
    ref = sym_run(sf, env, corpus, spec, L, max_steps=128)

    # segmented: 64 steps -> checkpoint -> reload -> continue 64
    mid = sym_run(sf, env, corpus, spec, L, max_steps=64)
    path = str(tmp_path / "ck.npz")
    save_frontier(path, mid, {"tx": 0, "steps_done": 64})
    template = _build()[0]
    loaded, meta = load_frontier(path, template)
    assert meta == {"tx": 0, "steps_done": 64}
    assert _equal_trees(mid, loaded), "round-trip must be lossless"
    out = sym_run(loaded, env, corpus, spec, L, max_steps=64)
    assert _equal_trees(ref, out), "resumed run must match uninterrupted"


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    sf, _, _ = _build(P=8)
    path = str(tmp_path / "ck.npz")
    save_frontier(path, sf)
    import pytest

    with pytest.raises(ValueError):
        load_frontier(path, _build(P=16)[0])


def test_wrapper_writes_checkpoints(tmp_path):
    SymExecWrapper(
        [BRANCHY], limits=L, lanes_per_contract=4, max_steps=64,
        transaction_count=1, checkpoint_dir=str(tmp_path / "ckpts"),
        deadline_chunk_steps=64,
    )
    assert os.path.exists(str(tmp_path / "ckpts" / "frontier.npz"))


# --- durability: rotation, torn writes, typed corruption --------------


def test_save_rotates_last_known_good(tmp_path):
    sf, env, corpus = _build()
    a = sym_run(sf, env, corpus, SymSpec(), L, max_steps=32)
    b = sym_run(a, env, corpus, SymSpec(), L, max_steps=32)
    path = str(tmp_path / "ck.npz")
    save_frontier(path, a, {"steps_done": 32})
    save_frontier(path, b, {"steps_done": 64})
    assert os.path.exists(path + ".1")
    template = _build()[0]
    newest, meta = load_frontier(path, template)
    assert meta["steps_done"] == 64 and _equal_trees(b, newest)
    prev, meta1 = load_frontier(path + ".1", template)
    assert meta1["steps_done"] == 32 and _equal_trees(a, prev)


def test_torn_write_detected_and_falls_back(tmp_path):
    """Kill-during-checkpoint-write: truncating the npz at several byte
    offsets must raise the TYPED corruption error, and the resilient
    loader must fall back to the rotated last-known-good copy."""
    sf, env, corpus = _build()
    good = sym_run(sf, env, corpus, SymSpec(), L, max_steps=32)
    newer = sym_run(good, env, corpus, SymSpec(), L, max_steps=32)
    path = str(tmp_path / "ck.npz")
    save_frontier(path, good, {"steps_done": 32})
    save_frontier(path, newer, {"steps_done": 64})
    raw = open(path, "rb").read()
    template = _build()[0]
    # several tear points: header-only, mid-archive, digest chopped
    for cut in (10, len(raw) // 3, len(raw) // 2, len(raw) - 40,
                len(raw) - 1):
        with open(path, "wb") as fh:
            fh.write(raw[:cut])
        with pytest.raises(CheckpointCorrupt):
            load_frontier(path, template)
        tree, meta, src = load_frontier_resilient(path, template)
        assert src == path + ".1"
        assert meta["steps_done"] == 32 and _equal_trees(good, tree)
    # flipped byte mid-payload: whole-file sha must catch it
    flipped = bytearray(raw)
    flipped[len(raw) // 2] ^= 0xFF
    with open(path, "wb") as fh:
        fh.write(bytes(flipped))
    with pytest.raises(CheckpointCorrupt):
        load_frontier(path, template)


def test_dtype_mismatch_is_typed_corruption(tmp_path):
    sf, _, _ = _build()
    path = str(tmp_path / "ck.npz")
    # same shapes, wrong dtype on one leaf: must be CheckpointCorrupt
    # (satellite: not a bare ValueError), distinct from shape mismatch
    import jax.numpy as jnp

    bad = sf.replace(base=sf.base.replace(
        pc=sf.base.pc.astype(jnp.int64)))
    save_frontier(path, bad)
    with pytest.raises(CheckpointCorrupt, match="dtype"):
        load_frontier(path, _build()[0])


def test_missing_leaf_is_typed_corruption(tmp_path):
    import io
    import zipfile

    sf, _, _ = _build()
    path = str(tmp_path / "ck.npz")
    save_frontier(path, sf)
    # rewrite as a v1-style archive (no schema, no trailer) with one
    # leaf dropped — the loader must name the missing leaf
    raw = open(path, "rb").read()
    body = raw[:-74]
    zin = zipfile.ZipFile(io.BytesIO(body))
    out = io.BytesIO()
    with zipfile.ZipFile(out, "w") as zout:
        names = [n for n in zin.namelist() if "::" in n]
        for n in zin.namelist():
            if n == names[0] or n.startswith("__schema__"):
                continue
            zout.writestr(n, zin.read(n))
    with open(path, "wb") as fh:
        fh.write(out.getvalue())
    with pytest.raises(CheckpointCorrupt, match="missing leaf"):
        load_frontier(path, _build()[0])


def test_v1_unversioned_npz_still_loads(tmp_path):
    """Old-format files (raw savez, no schema / digests / trailer) must
    keep loading: a long campaign may resume across this upgrade."""
    import jax

    sf, _, _ = _build()
    path = str(tmp_path / "old.npz")
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(sf)
    arrays = {}
    for i, (p, leaf) in enumerate(leaves_with_path):
        name = "/".join(str(getattr(k, "name", getattr(k, "idx", k)))
                        for k in p)
        arrays[f"leaf{i}::{name}"] = np.asarray(leaf)
    arrays["__meta__"] = np.frombuffer(
        json.dumps({"tx": 3}).encode(), dtype=np.uint8)
    with open(path, "wb") as fh:
        np.savez_compressed(fh, **arrays)
    loaded, meta = load_frontier(path, _build()[0])
    assert meta == {"tx": 3}
    assert _equal_trees(sf, loaded)


# --- campaign (JSON) checkpoint durability ----------------------------


def test_json_checkpoint_roundtrip_rotation_and_fallback(tmp_path):
    p = str(tmp_path / "campaign.json")
    save_json_checkpoint(p, {"next_batch": 1, "issues": []})
    save_json_checkpoint(p, {"next_batch": 2, "issues": ["x"]})
    assert load_json_checkpoint(p)["next_batch"] == 2
    assert load_json_checkpoint(p + ".1")["next_batch"] == 1
    raw = open(p, "rb").read()
    for cut in (0, 5, len(raw) - 2):
        with open(p, "wb") as fh:
            fh.write(raw[:cut])
        with pytest.raises(CheckpointCorrupt):
            load_json_checkpoint(p)
        state, src = load_json_checkpoint_resilient(p)
        assert src == p + ".1" and state["next_batch"] == 1
    # checksum catches a bit-rotted payload that still parses as JSON
    doc = json.loads(raw.decode())
    doc["state"]["next_batch"] = 99
    with open(p, "w") as fh:
        json.dump(doc, fh)
    with pytest.raises(CheckpointCorrupt, match="sha256"):
        load_json_checkpoint(p)


def test_json_checkpoint_v1_and_fresh_start(tmp_path):
    # v1: a bare state dict loads as-is
    p = str(tmp_path / "campaign.json")
    with open(p, "w") as fh:
        json.dump({"next_batch": 7}, fh)
    assert load_json_checkpoint(p) == {"next_batch": 7}
    # no file at all: resilient loader reports a fresh start
    state, src = load_json_checkpoint_resilient(str(tmp_path / "no.json"))
    assert state is None and src is None
    # first-ever checkpoint torn with no rotated copy: fresh start too
    p2 = str(tmp_path / "torn.json")
    with open(p2, "w") as fh:
        fh.write('{"__schema__": 2, "sha')
    state, src = load_json_checkpoint_resilient(p2)
    assert state is None and src is None
