"""Corpus campaign driver (BASELINE configs 2-3):
constant-shape batches, one compiled engine, checkpoint/resume."""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.mythril.campaign import CorpusCampaign, load_corpus_dir
from mythril_tpu.utils.checkpoint import (load_json_checkpoint,
                                          save_json_checkpoint)

KILLABLE = assemble(0, "SELFDESTRUCT")
SAFE = assemble(1, 0, "SSTORE", "STOP")


def write_corpus(tmp_path, n=6):
    d = tmp_path / "corpus"
    d.mkdir()
    for i in range(n):
        code = KILLABLE if i % 2 == 0 else SAFE
        (d / f"c{i:03d}.hex").write_text(code.hex())
    return str(d)


def make_campaign(corpus_dir, ckpt=None):
    return CorpusCampaign(
        load_corpus_dir(corpus_dir),
        batch_size=4,               # 6 contracts -> 2 batches (tail padded)
        lanes_per_contract=8,
        limits=TEST_LIMITS,
        max_steps=64,
        transaction_count=1,
        modules=["AccidentallyKillable"],
        checkpoint_dir=ckpt,
    )


def test_campaign_batches_and_metrics(tmp_path):
    corpus = write_corpus(tmp_path)
    res = make_campaign(corpus).run()
    assert res.batches == 2 and res.contracts == 6
    d = res.as_dict()
    assert d["contracts_per_sec"] > 0 and d["wall_sec"] > 0
    assert "attempts" in d["solver"]
    # 3 killable contracts, none from padding stubs
    bad = {i["contract"] for i in res.issues}
    assert bad == {"c000", "c002", "c004"}, bad
    assert all(i["swc-id"] == "106" for i in res.issues)


def test_campaign_checkpoint_resume(tmp_path):
    corpus = write_corpus(tmp_path)
    ck = str(tmp_path / "ck")
    full = make_campaign(corpus, ckpt=ck).run()
    assert full.batches == 2

    # a finished checkpoint resumes to a no-op, results preserved
    again = make_campaign(corpus, ckpt=ck).run()
    assert again.batches == 2
    assert len(again.issues) == len(full.issues)

    # rewind the cursor to mid-corpus: exactly one batch re-runs (the
    # rewrite goes through the checksummed writer — a hand-edited raw
    # file would be rejected as corrupt, which is the durability layer
    # doing its job)
    p = f"{ck}/campaign.json"
    state = load_json_checkpoint(p)
    state["next_batch"] = 1
    state["issues"] = [i for i in state["issues"] if i["batch"] < 1]
    state["batch_wall"] = state["batch_wall"][:1]
    save_json_checkpoint(p, state)
    resumed = make_campaign(corpus, ckpt=ck).run()
    assert resumed.batches == 2
    assert ({i["contract"] for i in resumed.issues}
            == {i["contract"] for i in full.issues})


def test_campaign_multihost_shard_and_merge(tmp_path):
    """Two 'hosts' each analyze a strided corpus shard; the merged result
    matches the single-host run issue-for-issue (SURVEY §5.8 corpus
    sharding — the one communication the corpus layer needs)."""
    from mythril_tpu.mythril.campaign import merge_campaigns

    corpus = write_corpus(tmp_path)
    single = make_campaign(corpus).run()

    def host(i):
        return CorpusCampaign(
            load_corpus_dir(corpus),
            batch_size=4, lanes_per_contract=8, limits=TEST_LIMITS,
            max_steps=64, transaction_count=1,
            modules=["AccidentallyKillable"],
            checkpoint_dir=str(tmp_path / "ck_mh"),  # SHARED dir
            num_hosts=2, host_index=i,
        )

    r0, r1 = host(0).run(), host(1).run()
    assert r0.contracts == 3 and r1.contracts == 3
    d0, d1 = r0.as_dict(), r1.as_dict()
    d0["issues_detail"], d1["issues_detail"] = r0.issues, r1.issues
    merged = merge_campaigns([d0, d1])
    assert merged["hosts"] == 2
    assert merged["contracts"] == single.contracts
    assert ({i["contract"] for i in merged["issues_detail"]}
            == {i["contract"] for i in single.issues})
    assert merged["solver"]["attempts"] > 0
    # per-host checkpoints coexist in the shared dir; the name embeds
    # BOTH shard coordinates so different fleet widths never collide
    assert (tmp_path / "ck_mh" / "campaign_host0of2.json").exists()
    assert (tmp_path / "ck_mh" / "campaign_host1of2.json").exists()


def test_campaign_host_index_validation(tmp_path):
    import pytest

    corpus = write_corpus(tmp_path)
    with pytest.raises(ValueError, match="host_index"):
        CorpusCampaign(load_corpus_dir(corpus), num_hosts=2, host_index=2)


def test_only_a_pipelined_first_attempt_is_handed_a_bundle(tmp_path):
    """The look-ahead lives in the pipelined loop alone: the serial
    loop, a degrade rung and the batch after a drain build for
    themselves (``_explore_batch`` gets no ``build``)."""
    from mythril_tpu.resilience import FaultInjector

    corpus = write_corpus(tmp_path, n=14)

    def run(pipeline):
        camp = CorpusCampaign(
            load_corpus_dir(corpus), batch_size=4, lanes_per_contract=8,
            limits=TEST_LIMITS, max_steps=64, transaction_count=1,
            modules=["AccidentallyKillable"], pipeline=pipeline,
            fault_injector=FaultInjector.from_string(
                "oom:batch=1:times=1"))
        seen, built = [], []
        explore, build = camp._explore_batch, camp._build_batch

        def spy(bi, names, codes, lanes=None, width=None, creations=None,
                **kw):
            seen.append((bi, lanes, kw.get("build") is not None))
            # (the rung's narrower shape is not what is under test:
            # explore at the warm one)
            return explore(bi, names, codes, creations=creations, **kw)

        def spy_build(items, tctx=None):
            built.append(items[0][0])
            return build(items, tctx)

        camp._explore_batch, camp._build_batch = spy, spy_build
        return camp.run(), seen, built

    serial, seen, built = run(False)
    assert serial.batch_status == ["ok", "ok-degraded:halve-lanes", "ok",
                                   "ok"]
    assert seen == [(0, None, False), (1, 4, False), (2, None, False),
                    (3, None, False)] and built == []
    piped, seen, built = run(True)
    assert piped.batch_status == serial.batch_status
    assert sorted(i["contract"] for i in piped.issues) == sorted(
        i["contract"] for i in serial.issues)
    # batch 1's bundle was built beside batch 0's calls and dropped at
    # the drain; batch 2 follows a drain; batch 3 was built beside 2's
    assert built == ["c004", "c012"]
    assert seen == [(0, None, False), (1, 4, False), (2, None, False),
                    (3, None, True)]
