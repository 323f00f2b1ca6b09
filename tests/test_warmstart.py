"""Fleet compile-artifact store + AOT prewarm (docs/serving.md
"Compile artifacts & prewarm", ISSUE 20): the durable shape-bucket
registry's crash/corruption contract, the prewarm IPC verb, the
recovery triggers (worker respawn, tier re-promotion), failure
degrading to lazy compile, prewarm yielding to live traffic, and the
acceptance path — a restarted daemon on the same data dir answering a
fresh same-shape submission with ``engine_compiles_total`` flat.

(Named test_warmstart so it sorts late: the tier-1 wall-clock budget
kills the suite mid-run, and new files must not displace the seed
prefix — see CHANGES.md PR 19.)
"""

import json
import os
import sys
import time

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu import engine_worker
from mythril_tpu.compilestore import (CompileStore, bucket_name,
                                      _parse_name,
                                      semantic_config_hash)
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.mythril.campaign import CorpusCampaign
from mythril_tpu.obs import metrics as obs_metrics
from mythril_tpu.resilience import WorkerSupervisor

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))


def counter(name):
    return obs_metrics.REGISTRY.counter(name).value


def stub_supervisor(**kw):
    kw.setdefault("stub", True)
    kw.setdefault("batch_timeout", 30.0)
    kw.setdefault("backoff_base", 0.01)
    kw.setdefault("spawn_timeout", 60.0)
    return WorkerSupervisor(**kw)


def stub_campaign(sup, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("lanes_per_contract", 4)
    kw.setdefault("max_steps", 16)
    kw.setdefault("transaction_count", 1)
    return CorpusCampaign([], limits=TEST_LIMITS,
                          worker_isolation="on", worker_supervisor=sup,
                          **kw)


# --- registry units -----------------------------------------------------

def test_bucket_name_roundtrip_and_config_hash():
    name = bucket_name("tpu", (4, 32, 256, 2), "ab12cd34ef56ab12")
    assert name.endswith(".json")
    assert _parse_name(name) == ("tpu", (4, 32, 256, 2),
                                 "ab12cd34ef56ab12")
    assert _parse_name("garbage.json") is None
    assert _parse_name("a__1x2x3x4__zz.json.corrupt") is None
    # semantic identity: key order must not matter, values must
    h1 = semantic_config_hash({"a": 1, "b": [2, 3]})
    h2 = semantic_config_hash({"b": [2, 3], "a": 1})
    h3 = semantic_config_hash({"a": 1, "b": [2, 4]})
    assert h1 == h2 != h3 and len(h1) == 16


def test_record_create_then_merge(tmp_path):
    store = CompileStore(str(tmp_path))
    r1 = store.record("cpu", (2, 4, 16, 1), "c" * 16, chunks=(8,))
    assert r1["hits"] == 1 and r1["chunks"] == [8]
    # a second observation merges: hits bump, chunk union, created kept
    r2 = store.record("cpu", (2, 4, 16, 1), "c" * 16, chunks=(16,))
    assert r2["hits"] == 2 and r2["chunks"] == [8, 16]
    assert r2["created"] == r1["created"]
    (b,) = store.buckets()
    assert b["hits"] == 2 and b["tier"] == "cpu"
    assert store.warm_chunks("cpu", (2, 4, 16, 1), "c" * 16) == [8, 16]
    # tier/cfh filters
    assert store.buckets(tier="tpu") == []
    assert store.buckets(cfh="d" * 16) == []


def test_corrupt_newest_falls_back_to_rotated(tmp_path):
    store = CompileStore(str(tmp_path))
    store.record("cpu", (2, 4, 16, 1), "c" * 16, chunks=(8,))
    store.record("cpu", (2, 4, 16, 1), "c" * 16, chunks=(16,))
    path = os.path.join(str(tmp_path), "buckets",
                        bucket_name("cpu", (2, 4, 16, 1), "c" * 16))
    assert os.path.exists(path + ".1")     # merge rotated a copy
    with open(path, "w") as fh:
        fh.write('{"torn":')               # kill -9 mid-write
    c0 = counter("compile_store_corrupt_total")
    (b,) = CompileStore(str(tmp_path)).buckets()
    # the rotated last-known-good answered; the tear was quarantined
    assert b["hits"] == 1 and b["chunks"] == [8]
    assert os.path.exists(path + ".corrupt")
    assert counter("compile_store_corrupt_total") == c0 + 1
    assert CompileStore(str(tmp_path)).stats()[
        "corrupt_quarantined"] >= 1
    # schema drift is corruption too, not a crash
    with open(path, "w") as fh:
        json.dump({"schema": 999, "shape": [1], "hits": "no"}, fh)
    (b,) = CompileStore(str(tmp_path)).buckets()
    assert b["hits"] == 1


def test_recency_cap_evicts_oldest(tmp_path):
    store = CompileStore(str(tmp_path), cap=3)
    e0 = counter("compile_store_evicted_total")
    for w in range(5):
        store.record("cpu", (w + 1, 4, 16, 1), "c" * 16)
        time.sleep(0.01)                   # distinct last_seen
    bks = store.buckets()
    assert len(bks) == 3
    # the two OLDEST shape classes went; the newest three remain
    assert sorted(b["shape"][0] for b in bks) == [3, 4, 5]
    assert counter("compile_store_evicted_total") == e0 + 2


def test_gc_sweeps_tmps_and_aged_corpses(tmp_path, monkeypatch):
    # the XLA cache is placed from outside (mythril_tpu/compile_cache):
    # point it at a scratch dir so the cache-ttl sweep prunes only that
    xla = tmp_path / "xla"
    xla.mkdir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(xla))
    store = CompileStore(str(tmp_path))
    store.record("cpu", (2, 4, 16, 1), "c" * 16)
    bdir = os.path.join(str(tmp_path), "buckets")
    old = time.time() - 7200.0            # older than the gc ttl
    for fn in ("stale.json.123.tmp", "dead.json.corrupt"):
        p = os.path.join(bdir, fn)
        with open(p, "w") as fh:
            fh.write("x")
        os.utime(p, (old, old))
    # an aged cache entry for the cache-ttl sweep
    ce = os.path.join(store.xla_cache_dir(), "entry-old")
    with open(ce, "w") as fh:
        fh.write("x")
    os.utime(ce, (old, old))
    rep = store.gc(ttl=3600.0, cache_ttl=60.0)
    assert rep["swept"] >= 2               # the tmp and the corpse
    assert rep["cache_pruned"] == 1 and not os.path.exists(ce)
    assert rep["buckets"] == 1             # the live bucket survived
    # ttl eviction: everything idle longer than 0s goes
    time.sleep(0.01)
    rep = store.gc(ttl=0.001)
    assert rep["expired"] == 1 and store.buckets() == []


def test_store_admin_compile_subcommands(tmp_path):
    import store_admin

    store = CompileStore(str(tmp_path))
    store.record("cpu", (2, 4, 16, 1), "c" * 16, chunks=(8, 16))
    stats = store_admin.cmd_compile_stats(str(tmp_path))
    assert stats["buckets"] == 1 and stats["tiers"] == {"cpu": 1}
    assert stats["chunks_total"] == 2
    rep = store_admin.cmd_compile_gc(str(tmp_path), max_buckets=0)
    assert rep["evicted"] == 1
    assert store_admin.cmd_compile_stats(str(tmp_path))["buckets"] == 0


# --- prewarm verb + triggers --------------------------------------------

def test_prewarm_verb_stub_roundtrip():
    sup = stub_supervisor()
    try:
        out = sup.prewarm([{"lanes": 4, "width": 2},
                           {"lanes": 8, "width": 2, "chunks": [8]}])
        assert out["done"] == 2 and out["total"] == 2 and out["stub"]
        assert out["warm_chunks"] == [[], []]
        # the worker survived the verb and still answers batches
        bat = sup.run_batch(0, ["a"], [b"\x00"])
        assert bat["paths"] == 1
    finally:
        sup.close()


def test_prewarm_failure_degrades_to_lazy(tmp_path):
    """A bucket the worker rejects must be skipped LOUDLY — the pass
    finishes the rest and the campaign keeps serving (degrade to lazy
    compile, never abort)."""
    sup = stub_supervisor()
    camp = stub_campaign(sup)
    try:
        store = CompileStore(str(tmp_path))
        cfh = camp.semantic_hash()
        tier = camp._active_tier()
        # the poison bucket is HOTTER, so the pass hits it first —
        # proving the good bucket still ran after the failure
        store.record(tier, (0, 4, 16, 1), cfh)
        store.record(tier, (0, 4, 16, 1), cfh)
        store.record(tier, (2, 4, 16, 1), cfh, chunks=(8,))
        camp.attach_compile_store(store, cfh=cfh)
        f0 = counter("prewarm_failures_total")
        st = camp.prewarm_from_store()
        assert st["total"] == 2 and st["done"] == 1
        assert st["state"] == "failed"
        assert "non-positive" in st["last_error"]
        assert counter("prewarm_failures_total") == f0 + 1
        kinds = [e["kind"] for e in camp._events]
        assert "prewarm_failed" in kinds and "prewarm_started" in kinds
        assert camp.prewarm_status()["state"] == "failed"
        # the worker is alive and the campaign still serves batches
        assert sup.run_batch(0, ["a"], [b"\x00"])["paths"] == 1
    finally:
        camp.close_worker()


def test_prewarm_yields_to_live_traffic(tmp_path, monkeypatch):
    """``should_stop`` is consulted between buckets: a pass preempted
    by live work stops where it is and re-arms ``_prewarm_pending`` —
    prewarm never holds up serving."""
    sup = stub_supervisor()
    camp = stub_campaign(sup)
    try:
        store = CompileStore(str(tmp_path))
        cfh = camp.semantic_hash()
        tier = camp._active_tier()
        for w in (1, 2, 3, 4):
            store.record(tier, (w, 4, 16, 1), cfh, chunks=(8,))
        camp.attach_compile_store(store, cfh=cfh)
        done = []
        orig = camp.prewarm_bucket
        monkeypatch.setattr(
            camp, "prewarm_bucket",
            lambda b: (done.append(b["shape"]), orig(b)) and None)
        st = camp.prewarm_from_store(
            should_stop=lambda: len(done) >= 2)
        assert st["state"] == "yielded" and st["done"] == 2
        assert len(done) == 2              # buckets 3+4 never started
        assert camp._prewarm_pending       # re-armed for the idle loop
        st = camp.prewarm_from_store()     # idle again: drains fully
        assert st["state"] == "done" and st["done"] == 4
        assert not camp._prewarm_pending
    finally:
        camp.close_worker()


def test_recovery_triggers_flag_prewarm():
    """Worker respawn and tier re-promotion — the two recovery events
    whose fresh process/backend compiles cold — must both re-arm the
    background prewarm pass."""
    from mythril_tpu.backend import TierManager

    tm = TierManager(tiers=("tpu", "cpu"),
                     probe_fn=lambda t, timeout: (True, "up"),
                     env_pin=False, auto_prober=False,
                     sticky_window=0.0, probe_every=0.0)
    camp = CorpusCampaign([], limits=TEST_LIMITS, batch_size=2,
                          lanes_per_contract=4, max_steps=16,
                          tier_manager=tm)
    camp._tier_sync()                      # settle the starting tier
    camp._prewarm_pending = False
    tm.demote("chaos")
    camp._tier_sync()
    assert camp._prewarm_pending           # tier transition re-arms
    camp._prewarm_pending = False
    camp._worker_event("worker_restart")
    assert camp._prewarm_pending           # fresh worker re-arms


def test_stub_batches_record_buckets_and_warm_counts(tmp_path):
    """Every executed batch records its shape bucket; ``warm_counts``
    feeds the heartbeat's ``warm a/b`` token."""
    sup = stub_supervisor()
    camp = stub_campaign(sup)
    try:
        store = CompileStore(str(tmp_path))
        camp.attach_compile_store(store)
        assert camp.warm_counts() == (0, 0)
        camp.run_external_batch([("a", b"\x00"), ("b", b"\x01")])
        (b,) = store.buckets()
        assert b["tier"] == camp._active_tier()
        assert b["shape"] == [2, 4, 16, 1]
        assert b["cfh"] == camp.semantic_hash()
        assert camp.warm_counts() == (1, 1)
    finally:
        camp.close_worker()


# --- corrupt-XLA-cache startup probe ------------------------------------

def test_cache_probe_quarantines_poisoned_dir(tmp_path, monkeypatch):
    """A cache flagged ``.dirty`` whose probe compile dies must be set
    aside ``.corrupt`` WHOLE (evidence preserved, never a silent wipe)
    and replaced with a fresh dir — the engine worker never runs
    through it."""
    cache = str(tmp_path / "xla_cache")
    os.makedirs(cache)
    with open(os.path.join(cache, "entry-0"), "wb") as fh:
        fh.write(b"\x00poison")
    with open(os.path.join(cache, ".dirty"), "w") as fh:
        fh.write("pid=1 t=0\n")
    monkeypatch.setenv("MYTHRIL_CACHE_PROBE_FAULT", "segv")
    q0 = counter("compile_cache_quarantined_total")
    use = engine_worker._maybe_probe_cache(cache)
    assert use == cache and os.path.isdir(cache)
    assert os.listdir(cache) == []         # fresh dir, served cold
    assert os.path.exists(
        os.path.join(str(tmp_path), "xla_cache.corrupt", "entry-0"))
    assert counter("compile_cache_quarantined_total") == q0 + 1


def test_cache_probe_hang_counts_as_failure(tmp_path, monkeypatch):
    monkeypatch.setenv("MYTHRIL_CACHE_PROBE_FAULT", "hang")
    monkeypatch.setenv("MYTHRIL_CACHE_PROBE_TIMEOUT", "2")
    assert engine_worker.probe_cache(str(tmp_path)) is False


def test_cache_probe_untouched_without_marker(tmp_path, monkeypatch):
    """No ``.dirty`` flag, no forced probe: startup pays nothing."""
    cache = str(tmp_path / "xla_cache")
    os.makedirs(cache)
    monkeypatch.delenv("MYTHRIL_CACHE_PROBE", raising=False)
    # the fault hook proves the probe never even ran
    monkeypatch.setenv("MYTHRIL_CACHE_PROBE_FAULT", "segv")
    assert engine_worker._maybe_probe_cache(cache) == cache
    assert not os.path.exists(cache + ".corrupt")


def test_supervisor_flags_cache_dirty_on_worker_death(tmp_path,
                                                      monkeypatch):
    """An unclean worker death may have torn a cache write mid-entry:
    the supervisor flags the dir so the NEXT worker probes before
    trusting it."""
    import signal

    cache = str(tmp_path / "wk_cache")
    os.makedirs(cache)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    sup = stub_supervisor()
    try:
        sup.run_batch(0, ["a"], [b"\x00"])
        os.kill(sup.status()["pid"], signal.SIGKILL)
        with pytest.raises(Exception):
            sup.run_batch(1, ["b"], [b"\x01"])
    finally:
        sup.close()
    assert os.path.exists(os.path.join(cache, ".dirty"))


# --- one compile cache, placed from outside --------------------------------

def test_compile_cache_is_placed_from_outside(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, no code path of the repo sets
    ``jax_compilation_cache_dir`` (JAX reads the variable itself);
    unset, the in-process engine, the worker and the daemon's compile
    store all resolve ONE fixed path under the checkout."""
    import subprocess
    import sys

    from mythril_tpu import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax\n"
        "from mythril_tpu import compile_cache\n"
        "from mythril_tpu.compilestore import CompileStore\n"
        "seen = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (seen.append(k), real(k, v))\n"
        "inproc = compile_cache.enable()\n"
        "worker = compile_cache.cache_dir()\n"
        "daemon = CompileStore(sys.argv[1]).xla_cache_dir()\n"
        "assert inproc == worker == daemon, (inproc, worker, daemon)\n"
        "print(inproc)\n"
        "print('jax_compilation_cache_dir' in seen)\n"
        "print(jax.config.jax_compilation_cache_dir)\n" % repo)

    def run(env_dir):
        env = {k: v for k, v in os.environ.items()
               if k != compile_cache.ENV}
        if env_dir:
            env[compile_cache.ENV] = env_dir
        r = subprocess.run([sys.executable, "-c", child, str(tmp_path)],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    outside = str(tmp_path / "outside")
    assert run(outside) == [outside, "False", outside]
    fixed = os.path.join(repo, ".jax_cache")
    assert run(None) == [fixed, "True", fixed]
    # the store keeps its bucket registry under the data dir (data),
    # and nothing cache-shaped beside it
    assert os.listdir(str(tmp_path)) == ["buckets"]


# --- end to end: restart comes back warm --------------------------------

def test_e2e_restart_comes_back_warm(tmp_path):
    """The ISSUE 20 acceptance path: a daemon warms a shape class and
    stops; a SECOND daemon on the same data dir prewarms from the
    durable registry and answers a FRESH same-shape submission with
    ``engine_compiles_total`` flat and the warm-hit counter rising."""
    import serve_client
    from mythril_tpu.serve import AnalysisDaemon, ServeOptions

    opts = ServeOptions(batch_size=2, lanes_per_contract=8,
                        max_steps=64, transaction_count=1,
                        modules=["AccidentallyKillable"],
                        limits_profile="test")
    dd = str(tmp_path / "sd")

    dm = AnalysisDaemon(opts, data_dir=dd, port=0)
    dm.start()
    try:
        url = f"http://127.0.0.1:{dm.port}"
        warm = serve_client.get_result(
            url, serve_client.submit(
                url, [("a", assemble(0, "SELFDESTRUCT")),
                      ("b", assemble(1, 0, "SSTORE", "STOP"))])["id"],
            wait=300.0)
        assert warm["state"] == "done"
    finally:
        dm.shutdown("test")
    bdir = os.path.join(dd, "compile_store", "buckets")
    recs = [f for f in os.listdir(bdir) if f.endswith(".json")]
    assert recs, "no bucket recorded by the first daemon"

    compiles0 = counter("engine_compiles_total")
    dm2 = AnalysisDaemon(opts, data_dir=dd, port=0)
    dm2.start()
    try:
        deadline = time.monotonic() + 240.0
        pw = {}
        while time.monotonic() < deadline:
            pw = dm2.health().get("prewarm") or {}
            if pw.get("state") in ("done", "failed"):
                break
            time.sleep(0.1)
        assert pw.get("state") == "done" and pw.get("done", 0) >= 1
        # the prewarm pass itself replayed cache artifacts: flat
        assert counter("engine_compiles_total") == compiles0
        warm0 = counter("serve_warm_compile_hits_total")
        url = f"http://127.0.0.1:{dm2.port}"
        # fresh bytecodes (dedupe can't answer), same shape class
        fresh = serve_client.get_result(
            url, serve_client.submit(
                url, [("c", assemble(2, "SELFDESTRUCT")),
                      ("d", assemble(1, 2, "SSTORE", "STOP"))])["id"],
            wait=300.0)
        assert fresh["state"] == "done" and fresh["completed"] == 2
        by = {r["name"]: r for r in fresh["results"]}
        assert len(by["c"]["issues"]) == 1 and by["d"]["issues"] == []
        assert "served_from" not in by["c"]
        # the restarted daemon's first verdict compiled NOTHING new
        assert counter("engine_compiles_total") == compiles0
        assert counter("serve_warm_compile_hits_total") > warm0
        # and the registry learned from the new generation too
        a, b = dm2.scheduler.warm_counts()
        assert a >= 1 and b >= 1
    finally:
        dm2.shutdown("test")
