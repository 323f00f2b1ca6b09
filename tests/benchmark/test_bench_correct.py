"""``correct`` is decided by a comparison that has been shown to fail.

One sound run of the campaign cell at the test limits comes out correct;
then the same run with one guarantee of the configuration broken does
not: a detection module withheld, an answer dropped where it is
produced, and the host callbacks switched off (which turns the concrete
RIPEMD-160 of the ``precompile_gate`` pair into a havoc leaf). All of
them skip the harness's look for a chip and drive the rest of a run.
"""

import copy
import os

import pytest

from bench_paths import ROOT, load

run = load("run.py", "bench_run_correct")

#: the cell's own batch of 8 (one corpus unit; the warm-up takes both
#: sets), the corpus cut to the 512 bytes of code the test limits hold
SMALL = ["--limits-profile", "test", "--lanes-per-contract", "16",
         "--max-steps", "128"]
ALL_BUT_KILLABLE = (
    "ArbitraryJump,ArbitraryStorage,DelegateCallToUntrustedContract,"
    "DeprecatedOperations,EtherThief,Exceptions,ExternalCalls,"
    "IntegerArithmetics,MultipleSends,PredictableVariables,"
    "RequirementsViolation,StateChangeAfterCall,"
    "TransactionOrderDependence,TxOrigin,UncheckedRetval,UserAssertions")


def cell(extra=()):
    loaded = run.load_cell(ROOT, "fullsuite.campaign")
    loaded = copy.deepcopy(loaded)
    loaded.config["analyze_args"] += SMALL + list(extra)
    return loaded


def drive(loaded, seed):
    lines = []
    out = run.run_cell(ROOT, "fullsuite.campaign", seed, 2.0, False,
                       require_tpu=False, loaded=loaded, log=lines.append)
    return out, lines


def test_sound_run_is_correct():
    out, lines = drive(cell(), 2 ** 31 + 7)
    assert out["correct"] is True, lines
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"contracts_per_min", "setup_s"}
    assert out["metrics"]["contracts_per_min"]["value"] > 0
    assert out["device"]["platform"] == "cpu"    # named, never hidden
    # each number compared is printed beside its limit
    assert any("kind=precompile_gate_safe" in ln and "(limit 0)" in ln
               for ln in lines)
    assert any(ln.startswith("check programs compiled inside the "
                             "window: 0 (limit 0)") for ln in lines)


def test_a_withheld_detection_module_is_not_correct():
    out, lines = drive(cell(["-m", ALL_BUT_KILLABLE]), 11)
    assert out["correct"] is False
    assert out["failed"] > 0
    assert any(ln.startswith("wrong verdict") and "_kill " in ln
               and "missing=['106']" in ln for ln in lines), lines


def test_an_answer_dropped_where_it_is_produced_is_not_correct(monkeypatch):
    from mythril_tpu.mythril.campaign import CorpusCampaign

    harvest = CorpusCampaign._harvest_batch

    def lossy(self, bi, sym):
        out = harvest(self, bi, sym)
        out["issues"] = [i for i in out["issues"]
                         if "_mint_unchecked" not in i["contract"]]
        return out

    monkeypatch.setattr(CorpusCampaign, "_harvest_batch", lossy)
    out, lines = drive(cell(), 12)
    assert out["correct"] is False and out["failed"] > 0
    assert any("_mint_unchecked" in ln and "missing=['101']" in ln
               for ln in lines), lines


def test_precompile_gate_pair_flips_without_host_callbacks(monkeypatch):
    """With the natives degraded to havoc leaves the safe sibling's
    SELFDESTRUCT looks reachable. ``callbacks._CB_OK`` is cached per
    process and ``sym_run`` per shape: the variant runs at a lane count
    of its own, and the cached answer is put back."""
    from mythril_tpu.ops import callbacks

    monkeypatch.setenv("MYTHRIL_HOST_CALLBACKS", "0")
    monkeypatch.setattr(callbacks, "_CB_OK", None)
    out, lines = drive(cell(["--lanes-per-contract", "12"]), 13)
    assert out["correct"] is False
    assert any("precompile_gate_safe" in ln and "extra=['106']" in ln
               for ln in lines), lines
    assert not any(ln.startswith("wrong verdict") and "missing" in ln
                   and "_precompile_gate " in ln for ln in lines)
    assert any(ln.startswith("check host_callbacks: False (limit True)")
               for ln in lines)
