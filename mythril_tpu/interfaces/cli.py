"""``myth``-style command line (reference: ``mythril/interfaces/cli.py``
⚠unv, SURVEY.md §2 row "CLI").

Commands: ``analyze`` (``a``), ``disassemble`` (``d``),
``list-detectors``, ``version``. Flag names follow the reference where
the concept carries over (``-t``, ``-m``, ``-o``, ``--loop-bound``,
``--execution-timeout``); TPU-frontier knobs (``--max-steps``,
``--lanes-per-contract``) replace the reference's per-state depth flags.

Run as ``python -m mythril_tpu <command> ...``.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def create_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mythril_tpu",
        description="TPU-native symbolic-execution security analyzer for EVM bytecode",
    )
    sub = p.add_subparsers(dest="command")

    def add_input_flags(cmd):
        cmd.add_argument("-f", "--codefile", metavar="PATH",
                         help="file holding runtime bytecode as hex")
        cmd.add_argument("-c", "--code", metavar="HEX",
                         help="runtime bytecode as a hex string")
        cmd.add_argument("--creation-code", metavar="PATH",
                         help="file holding CREATION bytecode as hex; enables "
                              "the constructor transaction")
        cmd.add_argument("--artifact", metavar="PATH",
                         help="solc standard-JSON output artifact (loads all "
                              "contracts with source maps)")
        cmd.add_argument("--solc-input", metavar="PATH",
                         help="solc standard-JSON INPUT (source text for line "
                              "numbers; used with --artifact)")
        cmd.add_argument("--name", default="MAIN", help="contract display name")

    a = sub.add_parser("analyze", aliases=["a"], help="symbolically analyze bytecode")
    add_input_flags(a)
    a.add_argument("-t", "--transaction-count", type=int, default=2,
                   help="number of attacker message-call transactions")
    a.add_argument("-m", "--modules", metavar="LIST",
                   help="comma-separated detection-module allow list")
    a.add_argument("-o", "--outform",
                   choices=["text", "markdown", "json", "jsonv2"],
                   default="text")
    a.add_argument("--max-steps", type=int, default=512,
                   help="superstep budget per transaction")
    a.add_argument("--max-depth", type=int, default=None,
                   help="reference-name alias: per-path instruction depth "
                        "== frontier superstep budget (overrides "
                        "--max-steps when given)")
    a.add_argument("--call-depth-limit", type=int, default=None,
                   help="max nested CALL/CREATE frames per lane (reference "
                        "default 3; here the frontier frame-stack cap)")
    a.add_argument("--lanes-per-contract", type=int, default=64,
                   help="frontier lanes (seed + fork headroom) per contract")
    a.add_argument("--loop-bound", type=int, default=None,
                   help="max taken backward jumps per loop target (bounded-"
                        "loops policy)")
    a.add_argument("--solver-iters", type=int, default=400,
                   help="witness-search repair iterations per query")
    a.add_argument("--solver-timeout", type=int, default=None, metavar="MS",
                   help="wall-clock budget per solver query, milliseconds "
                        "(reference units); expiry degrades to no-issue")
    a.add_argument("--parallel-solving", action="store_true",
                   help="run detection modules concurrently (thread pool "
                        "over the GIL-releasing native tape evaluator)")
    a.add_argument("--execution-timeout", type=float, default=None,
                   help="wall-clock budget in seconds for the exploration")
    a.add_argument("--create-timeout", type=float, default=None,
                   help="wall-clock budget in seconds for the CREATION "
                        "transaction (constructor) only")
    a.add_argument("--strategy",
                   choices=["bfs", "dfs", "naive-random", "weighted-random",
                            "coverage", "beam"],
                   default="bfs",
                   help="fork-admission policy when frontier slots run "
                        "short (the frontier itself steps breadth-first): "
                        "bfs=fifo, dfs=deepest-first, naive-random="
                        "unbiased hash order, weighted-random="
                        "depth-weighted hash, coverage=unvisited-target "
                        "first, beam=capped shallowest-first")
    a.add_argument("--limits-profile", choices=["default", "test"],
                   default="default",
                   help="frontier shape caps: 'test' compiles a much "
                        "smaller engine (CI / quick scans)")
    a.add_argument("--concrete-storage", action="store_true",
                   help="model unknown storage as zero instead of symbolic "
                        "(reference default; symbolic is --unconstrained-storage there)")
    a.add_argument("--unconstrained-storage", action="store_true",
                   help="model unknown storage as fully symbolic (this "
                        "engine's default; the reference flag name, kept "
                        "for parity — conflicts with --concrete-storage)")
    a.add_argument("--graph", metavar="PATH",
                   help="write the contract CFG with explored blocks "
                        "highlighted: *.html gets a self-contained "
                        "interactive page, anything else graphviz DOT")
    a.add_argument("--statespace-json", metavar="PATH",
                   help="dump the explored statespace as JSON: per-tx "
                        "surviving paths (pc, depth, constraints) + "
                        "per-contract instruction coverage")
    a.add_argument("--enable-iprof", action="store_true",
                   help="print a per-opcode executed-instruction profile "
                        "after the report")
    a.add_argument("--plugin-dir", metavar="DIR",
                   help="load external plugins (detection modules and/or "
                        "laser plugins) from every *.py in DIR; installed "
                        "entry-point plugins load automatically")

    a.add_argument("--corpus", metavar="DIR",
                   help="campaign mode: analyze every *.hex/*.bin under "
                        "DIR in constant-shape batches (one compiled "
                        "engine), with checkpoint/resume; prints a "
                        "throughput+issues JSON. X.bin beside "
                        "X.bin-runtime (solc --bin --bin-runtime) is one "
                        "contract whose constructor runs first")
    a.add_argument("--batch-size", type=int, default=32,
                   help="contracts per compiled batch (campaign mode)")
    a.add_argument("--checkpoint-dir", metavar="DIR",
                   help="campaign checkpoint directory (resume-able)")
    a.add_argument("--batch-timeout", type=float, default=None,
                   metavar="SEC",
                   help="campaign mode: hard wall-clock watchdog per "
                        "batch — a hung compile or wedged device call "
                        "becomes a batch failure (retried, then bisected "
                        "to quarantine the poison contract) instead of "
                        "an indefinite stall")
    a.add_argument("--init-timeout", type=float, default=None,
                   metavar="SEC",
                   help="campaign mode: probe backend init in a "
                        "subprocess with this deadline BEFORE loading "
                        "the engine; on failure fall back to the CPU "
                        "backend and record the event in the report")
    a.add_argument("--max-batch-retries", type=int, default=1,
                   metavar="N",
                   help="campaign mode: whole-batch re-attempts after a "
                        "failure before bisecting it (default 1)")
    a.add_argument("--fault-inject", metavar="SPEC",
                   help="campaign mode (testing): inject deterministic "
                        "faults, e.g. 'raise:contract=c002', "
                        "'hang:batch=1', 'raise:batch=0:times=1', "
                        "'kill:batch=2', 'oom:batch=1:times=2'; "
                        "';'-separated specs; the MYTHRIL_FAULT_INJECT "
                        "env var is equivalent")
    a.add_argument("--oom-ladder", metavar="LIST",
                   default=None,
                   help="campaign mode: comma-separated degradation "
                        "rungs walked (cumulatively) when a batch hits "
                        "RESOURCE_EXHAUSTED, from 'halve-lanes', "
                        "'halve-batch', 'cpu' (default: all three in "
                        "that order); 'none' disables degradation — an "
                        "OOM then falls to retry/bisect")
    a.add_argument("--pipeline", dest="pipeline", action="store_true",
                   default=True,
                   help="campaign mode (default ON): overlap batch i's "
                        "host phase (detection modules + witness "
                        "search) with batch i+1's device execution, "
                        "and write checkpoints from a background "
                        "thread; results are byte-identical to "
                        "--no-pipeline and any fault drains back to "
                        "the serial retry/bisect path (see "
                        "docs/performance.md)")
    a.add_argument("--no-pipeline", dest="pipeline", action="store_false",
                   help="campaign mode: strictly serial batches "
                        "(device and host phases never overlap)")
    a.add_argument("--solver-workers", type=int, default=1, metavar="N",
                   help="threads for the detection-module/witness-"
                        "search pool in the campaign host phase "
                        "(N>1 implies --parallel-solving with an "
                        "N-thread pool; default 1)")
    a.add_argument("--checkpoint-every", type=int, default=1,
                   metavar="N",
                   help="campaign mode: durable checkpoint write every "
                        "N batches (default 1 — kill -9 at any instant "
                        "loses at most one batch; larger N trades "
                        "replayed batches for less checkpoint I/O)")
    a.add_argument("--trace", metavar="FILE",
                   help="write a Chrome-trace JSON to FILE (load it in "
                        "Perfetto / chrome://tracing) plus an append-"
                        "only JSONL event log beside it (FILE with a "
                        ".jsonl suffix); spans cover supersteps, "
                        "batches, checkpoints, degrades — see "
                        "docs/observability.md and tools/trace_report.py")
    a.add_argument("--metrics", metavar="FILE",
                   help="write a metrics snapshot at exit: counters/"
                        "gauges/histograms (frontier occupancy, "
                        "fork/park/spill rates, solver checks, degrade "
                        "and compile events, checkpoint latency) as "
                        "JSON, or Prometheus text format when FILE "
                        "ends in .prom/.txt")
    a.add_argument("--heartbeat", type=float, default=None, metavar="SEC",
                   help="campaign mode: print a one-line progress "
                        "heartbeat to stderr at most every SEC seconds "
                        "(contracts done, paths/s, frontier occupancy, "
                        "degrade rung, last-checkpoint age)")
    a.add_argument("--fleet", metavar="DIR",
                   help="campaign mode: elastic fleet coordination via "
                        "a shared work-ledger directory (NFS/GCS): the "
                        "corpus is cut into leased work units, workers "
                        "claim/heartbeat/commit them, and a dead "
                        "host's units migrate to survivors (see "
                        "docs/fleet.md); replaces the static "
                        "--num-hosts/--host-index split")
    a.add_argument("--lease-ttl", type=float, default=60.0, metavar="SEC",
                   help="fleet mode: a unit lease whose heartbeat is "
                        "older than SEC is reclaimed by any live "
                        "worker (default 60)")
    a.add_argument("--unit-size", type=int, default=None, metavar="N",
                   help="fleet mode: contracts per work unit (rounded "
                        "up to whole batches; default: one batch) — "
                        "the granularity of reclaim and of loss when a "
                        "worker dies mid-unit")
    a.add_argument("--max-unit-leases", type=int, default=3, metavar="N",
                   help="fleet mode: lease grants per unit before it "
                        "is marked lost instead of retried forever "
                        "(default 3 — the fleet-level analog of "
                        "bisect-to-quarantine)")
    a.add_argument("--worker-id", metavar="ID", default=None,
                   help="fleet mode: stable worker identity stamped "
                        "into leases and unit results (default: "
                        "hostname-pid-tid)")
    a.add_argument("--solver-store", metavar="DIR",
                   help="shared per-QUERY solver verdict store "
                        "(docs/solver.md): canonical constraint hashes "
                        "-> durable sat/unsat verdicts, reused across "
                        "campaigns, fleet workers, and restarts. "
                        "Default: <fleet-dir>/solver_store under "
                        "--fleet, off otherwise")
    a.add_argument("--no-solver-store", action="store_true",
                   help="disable the solver verdict store (including "
                        "the --fleet default); the in-process LRU and "
                        "the refute/probe stages stay on")
    a.add_argument("--worker-isolation", choices=["on", "off", "auto"],
                   default="auto",
                   help="campaign mode: run device batches in a "
                        "supervised engine-worker SUBPROCESS so a "
                        "libtpu segfault / OOM kill / hard hang is a "
                        "worker restart (replayed through "
                        "retry/ladder/bisect), never process death; "
                        "N rapid deaths open a crash-loop breaker "
                        "that pins work to the in-process CPU path "
                        "(docs/resilience.md). auto (default) = on "
                        "under --fleet, off otherwise")
    a.add_argument("--backend-tiers", metavar="LIST", default=None,
                   help="campaign mode: ranked backend-tier ladder "
                        "(comma-separated from 'tpu', 'gpu', 'cpu'; "
                        "default: detect from the environment). A "
                        "crash-looping or lost backend DEMOTES to the "
                        "next tier instead of pinning to CPU, and a "
                        "background prober re-promotes when the "
                        "better tier probes healthy again "
                        "(docs/resilience.md \"Backend tiers\")")
    a.add_argument("--fleet-follow", action="store_true",
                   help="fleet mode: join a serve daemon's FEED ledger "
                        "(docs/serving.md) — units carry their own "
                        "bytecode, so no --corpus is needed; the "
                        "worker polls for newly fed units and exits "
                        "when the feeder closes the feed (or "
                        "--execution-timeout lapses)")
    a.add_argument("--num-hosts", type=int, default=0, metavar="N",
                   help="campaign mode: shard the corpus across N hosts; "
                        "this process analyzes slice --host-index "
                        "(default: jax.distributed process count when "
                        "initialized, else 1)")
    a.add_argument("--host-index", type=int, default=-1, metavar="I",
                   help="which corpus shard this host takes (default: "
                        "jax.distributed process index, else 0)")
    a.add_argument("-a", "--address", metavar="ADDRESS",
                   help="analyze the on-chain contract at ADDRESS "
                        "(requires --rpc)")
    a.add_argument("--no-onchain-callees", action="store_true",
                   help="with -a: skip the dynld pre-pass that fetches "
                        "code for the target's hardcoded callee "
                        "addresses (their calls then havoc soundly)")
    a.add_argument("--rpc", metavar="URI",
                   help="JSON-RPC endpoint; 'file:PATH' uses a JSON mock "
                        "({addr: {code, storage}})")

    d = sub.add_parser("disassemble", aliases=["d"], help="print EASM")
    add_input_flags(d)

    c = sub.add_parser("concolic",
                       help="flip branches of a concrete trace "
                            "(hybrid-fuzzing helper)")
    add_input_flags(c)
    c.add_argument("--input", metavar="TRACE.json",
                   help="reference-shaped concolic trace file "
                        "(initialState.accounts + steps); supplies "
                        "code/calldata/value/caller from the last step")
    c.add_argument("--calldata", metavar="HEX",
                   help="seed transaction calldata (required unless "
                        "--input is given)")
    c.add_argument("--callvalue", type=int, default=0)
    c.add_argument("--jump-addresses", metavar="LIST",
                   help="comma-separated JUMPI pcs to flip (default: all)")
    c.add_argument("--max-steps", type=int, default=256)
    c.add_argument("--solver-iters", type=int, default=400)
    c.add_argument("--limits-profile", choices=["default", "test"],
                   default="default")

    rs = sub.add_parser("read-storage",
                        help="read a live contract's storage slot over RPC")
    rs.add_argument("index", help="storage slot (int or 0xhex)")
    rs.add_argument("address", help="contract address")
    rs.add_argument("--rpc", required=True, metavar="URI")

    f2h = sub.add_parser("function-to-hash",
                         help="4-byte selector of a function signature")
    f2h.add_argument("signature", help='e.g. "transfer(address,uint256)"')

    h2a = sub.add_parser("hash-to-address",
                         help="EIP-55 address from a 32-byte storage word")
    h2a.add_argument("hashes", nargs="+", help="32-byte hex words")

    sf_ = sub.add_parser("safe-functions",
                         help="functions with no issues found")
    add_input_flags(sf_)
    sf_.add_argument("-t", "--transaction-count", type=int, default=2)
    sf_.add_argument("--max-steps", type=int, default=512)
    sf_.add_argument("--lanes-per-contract", type=int, default=64)
    sf_.add_argument("--limits-profile", choices=["default", "test"],
                     default="default")

    cm = sub.add_parser("campaign-merge",
                        help="merge per-host campaign JSON results into "
                             "corpus-level metrics")
    cm.add_argument("results", nargs="+", metavar="JSON|LEDGER",
                    help="campaign output files (one per host) and/or "
                         "fleet ledger directories (--fleet DIR): a "
                         "directory contributes every committed unit "
                         "result — including those of workers that "
                         "died before printing a report")
    cm.add_argument("--strict-coverage", action="store_true",
                    help="exit nonzero unless the merged coverage "
                         "manifest is full (every contract analyzed or "
                         "quarantined — nothing lost or unaccounted)")

    sv = sub.add_parser(
        "serve",
        help="always-on analysis daemon: admission queue, bytecode-"
             "hash dedupe, warm-compile reuse, streaming results "
             "(docs/serving.md)")
    sv.add_argument("--host", default="127.0.0.1",
                    help="bind address (default 127.0.0.1)")
    sv.add_argument("--port", type=int, default=8780,
                    help="bind port; 0 asks the OS for a free one "
                         "(see --port-file)")
    sv.add_argument("--port-file", metavar="PATH",
                    help="write the BOUND port to PATH once listening "
                         "(the --port 0 discovery channel for "
                         "supervisors and tests)")
    sv.add_argument("--data-dir", default="serve_data", metavar="DIR",
                    help="persistent serve state (the dedupe verdict "
                         "store lives in DIR/store); survives "
                         "restarts — that is the exactly-once story")
    sv.add_argument("--no-dedupe", dest="dedupe", action="store_false",
                    default=True,
                    help="escape hatch: always re-analyze, never "
                         "serve or write stored verdicts")
    sv.add_argument("--max-queue", type=int, default=4096, metavar="N",
                    help="admission queue depth bound; overflow gets "
                         "HTTP 429 (default 4096)")
    sv.add_argument("--tenant-rate", type=float, default=None,
                    metavar="R",
                    help="default per-tenant admission rate: a token "
                         "bucket of R fresh contracts/sec per tenant "
                         "(dedupe hits are free); breach gets HTTP "
                         "429 with Retry-After (default: unlimited)")
    sv.add_argument("--tenant-burst", type=int, default=None,
                    metavar="N",
                    help="default token-bucket capacity (default: "
                         "max(8, 2*rate))")
    sv.add_argument("--tenant-max-inflight", type=int, default=None,
                    metavar="N",
                    help="default per-tenant cap on queued+running "
                         "entries (default: unlimited)")
    sv.add_argument("--quota", action="append", default=None,
                    metavar="TENANT=RATE[:BURST[:INFLIGHT]]",
                    help="per-tenant quota override (repeatable); "
                         "blank fields mean unlimited, e.g. "
                         "--quota scanner=2:8:4 --quota ops=::64")
    sv.add_argument("--shed-depth-hi", type=float, default=0.85,
                    metavar="FRAC",
                    help="enter load shedding when queue depth "
                         "reaches FRAC of --max-queue (default 0.85); "
                         "low-priority submissions then get verdict-"
                         "store-only answers until pressure clears")
    sv.add_argument("--shed-age-hi", type=float, default=30.0,
                    metavar="SEC",
                    help="enter load shedding when the oldest queued "
                         "entry is SEC old (default 30)")
    sv.add_argument("--shed-priority-max", type=int, default=0,
                    metavar="P",
                    help="submissions with priority <= P are the "
                         "sheddable class (default 0 — the default "
                         "priority; pass a higher priority to keep a "
                         "lane under overload)")
    sv.add_argument("--no-shed", action="store_true",
                    help="disable the load-shedding ladder (overflow "
                         "then only ever 429s)")
    sv.add_argument("--follow", metavar="RPC_URI",
                    help="chain-head follower: poll eth_blockNumber "
                         "on RPC_URI, ingest newly deployed contracts "
                         "as the standing lowest-priority tenant "
                         "'follower' (shed first under overload); "
                         "resumes from a durable cursor in --data-dir")
    sv.add_argument("--follow-poll", type=float, default=2.0,
                    metavar="SEC",
                    help="follower poll cadence at the chain head "
                         "(default 2.0)")
    sv.add_argument("--backfill", metavar="RPC_URI",
                    help="whole-chain backfill: walk history BACKWARD "
                         "from the head anchored at first start, "
                         "ingesting every deployed contract as the "
                         "standing tenant 'backfill' at the lowest "
                         "priority of all (below the follower, shed "
                         "first); resumes from a durable two-ended "
                         "cursor in --data-dir")
    sv.add_argument("--backfill-window", type=int, default=64,
                    metavar="N",
                    help="blocks per backfill scan window; the cursor "
                         "advances only past fully-committed windows, "
                         "so a kill re-scans at most N blocks "
                         "(default 64)")
    sv.add_argument("--compact-every", type=float, default=None,
                    metavar="SEC",
                    help="background store compaction period: fold "
                         "settled loose verdict files into immutable "
                         "checksummed segments behind a "
                         "generation-numbered manifest "
                         "(docs/serving.md 'Verdict segments & edge "
                         "replicas'); run on at most ONE replica per "
                         "data dir (default: off)")
    sv.add_argument("--store-only", action="store_true",
                    help="edge replica mode: serve dedupe-store "
                         "answers only, NO engine — store misses get "
                         "a typed unknown-contract answer with "
                         "Retry-After; the manifest snapshot is "
                         "re-polled for new generations")
    sv.add_argument("--drain-timeout", type=float, default=30.0,
                    metavar="SEC",
                    help="SIGTERM drain budget: how long the in-flight "
                         "batch (or fed fleet units) may take before "
                         "the daemon abandons them and exits "
                         "(default 30)")
    sv.add_argument("--fleet", metavar="DIR",
                    help="front a multi-host fleet: append admitted "
                         "batches to a FEED work ledger in DIR instead "
                         "of running locally; workers join with "
                         "'analyze --fleet DIR --fleet-follow' "
                         "(docs/fleet.md, docs/serving.md)")
    sv.add_argument("--solver-store", metavar="DIR",
                    help="shared per-QUERY solver verdict store "
                         "(docs/solver.md); default: "
                         "<data-dir>/solver_store — the daemon's "
                         "solver work survives restarts like its "
                         "per-contract verdicts do")
    sv.add_argument("--no-solver-store", action="store_true",
                    help="disable the per-query solver verdict store "
                         "(the per-contract dedupe store is governed "
                         "by --no-dedupe, not this flag)")
    sv.add_argument("--batch-size", type=int, default=8,
                    help="contracts per compiled service batch "
                         "(default 8)")
    sv.add_argument("--lanes-per-contract", type=int, default=32)
    sv.add_argument("--max-steps", type=int, default=256,
                    help="default superstep budget per transaction "
                         "(overridable per request)")
    sv.add_argument("-t", "--transaction-count", type=int, default=1,
                    help="default attacker transactions (overridable "
                         "per request)")
    sv.add_argument("-m", "--modules", metavar="LIST",
                    help="default detection-module allow list "
                         "(overridable per request)")
    sv.add_argument("--limits-profile", choices=["default", "test"],
                    default="default")
    sv.add_argument("--solver-iters", type=int, default=400)
    sv.add_argument("--solver-timeout", type=int, default=None,
                    metavar="MS")
    sv.add_argument("--solver-workers", type=int, default=1, metavar="N")
    sv.add_argument("--batch-timeout", type=float, default=None,
                    metavar="SEC",
                    help="per-batch watchdog (same contract as "
                         "campaign mode)")
    sv.add_argument("--max-batch-retries", type=int, default=1,
                    metavar="N")
    sv.add_argument("--oom-ladder", metavar="LIST", default=None)
    sv.add_argument("--fault-inject", metavar="SPEC",
                    help="testing: deterministic faults in service "
                         "batches (batch indices count monotonically "
                         "over the daemon lifetime)")
    sv.add_argument("--concrete-storage", action="store_true")
    sv.add_argument("--worker-isolation",
                    choices=["on", "off", "auto"], default="auto",
                    help="run service batches in a supervised "
                         "engine-worker subprocess (auto = ON under "
                         "serve): backend death becomes a worker "
                         "restart, a crash loop opens a breaker that "
                         "pins the config to in-process CPU — "
                         "reported in /healthz degraded_configs "
                         "(docs/resilience.md)")
    sv.add_argument("--backend-tiers", metavar="LIST", default=None,
                    help="ranked backend-tier ladder for resident "
                         "campaigns (comma-separated from 'tpu', "
                         "'gpu', 'cpu'; default: detect). Each config "
                         "is a capacity class placed on whatever tier "
                         "its worker holds; demotions/re-promotions "
                         "surface in /healthz backend_tiers and the "
                         "engine_tier_* metrics (docs/serving.md)")
    sv.add_argument("--compile-store", metavar="DIR", default=None,
                    help="fleet compile-artifact store: durable "
                         "shape-bucket registry, so restarted/sibling "
                         "replicas and re-promoted tiers prewarm "
                         "through the shared persistent XLA cache "
                         "(JAX_COMPILATION_CACHE_DIR, else "
                         "<checkout>/.jax_cache) (default: "
                         "<data-dir>/compile_store; docs/serving.md "
                         "'Compile artifacts & prewarm')")
    sv.add_argument("--prewarm", dest="prewarm", action="store_true",
                    default=True,
                    help="AOT-prewarm the registry's hottest shape "
                         "buckets on daemon start, worker respawn, and "
                         "tier re-promotion (default: on; strictly "
                         "subordinate to live traffic)")
    sv.add_argument("--no-prewarm", dest="prewarm", action="store_false",
                    help="disable the background prewarm pass (the "
                         "compile store still records warm shapes and "
                         "the shared XLA cache still serves lazy "
                         "compiles)")
    sv.add_argument("--trace", metavar="FILE",
                    help="Chrome-trace + JSONL event log (admit/"
                         "queue_wait/schedule/stream spans ride the "
                         "same spine as batch spans)")
    sv.add_argument("--metrics", metavar="FILE",
                    help="metrics snapshot at exit (the live registry "
                         "is always scrapeable at /metrics)")
    sv.add_argument("--heartbeat", type=float, default=None,
                    metavar="SEC",
                    help="print a one-line serving heartbeat to stderr "
                         "every SEC seconds: queue depth, inflight, "
                         "store size, and end-to-end request latency "
                         "p50/p95 (serve_request_seconds)")

    ld = sub.add_parser("list-detectors",
                        help="list registered detection modules")
    ld.add_argument("--plugin-dir", metavar="DIR",
                    help="also load external plugins from DIR first")
    sub.add_parser("version", help="print version")
    return p


def _limits_for(args):
    """THE limits-resolution for a parsed argv — every consumer (analyze,
    campaign, the dynld prefetch cap) must share this one derivation, or
    a cap computed from a stale copy can desync from the real account
    table and silently disable cross-contract resolution."""
    import dataclasses

    from ..config import DEFAULT_LIMITS, TEST_LIMITS

    limits = (TEST_LIMITS if getattr(args, "limits_profile", None) == "test"
              else DEFAULT_LIMITS)
    if getattr(args, "call_depth_limit", None) is not None:
        limits = dataclasses.replace(limits,
                                     call_depth=args.call_depth_limit)
    return limits


def _load_contracts(args):
    from ..mythril import MythrilDisassembler

    if getattr(args, "address", None):
        if not getattr(args, "rpc", None):
            print("error: -a/--address requires --rpc", file=sys.stderr)
            raise SystemExit(2)
        from ..utils.loader import DynLoader, rpc_client_from_uri

        dl = DynLoader(rpc_client_from_uri(args.rpc))
        args._dynld = dl  # exec_analyze reuses this client for mid-run
        # loading instead of opening a second connection to the node
        target_addr = int(args.address, 16)
        code = dl.dynld(target_addr)
        if not code:
            print(f"error: no code at {args.address}", file=sys.stderr)
            raise SystemExit(2)
        target = MythrilDisassembler.load_from_bytecode(
            code.hex(), name=args.address)
        target.address = target_addr
        out = [target]
        if getattr(args, "no_onchain_callees", False):
            return out
        # dynamic loading of statically-referenced callees (pre-pass —
        # see DynLoader.prefetch_callees): their code joins the corpus
        # under their REAL addresses so hardcoded cross-contract calls
        # resolve instead of degrading to havoc. The prefetch is capped
        # to the frontier account table (2 reserved slots + target +
        # callees must fit max_accounts, or make_frontier falls to the
        # own-contract-only layout and NOTHING cross-contract resolves),
        # and a self-referencing PUSH20 must not duplicate the target.
        A = _limits_for(args).max_accounts
        room = max(0, A - 2 - 1)
        for addr, callee in dl.prefetch_callees(code, limit=room,
                                                exclude=(target_addr,)):
            c = MythrilDisassembler.load_from_bytecode(
                callee.hex(), name=f"0x{addr:040x}")
            c.address = addr
            out.append(c)
            print(f"dynld: loaded callee 0x{addr:040x} "
                  f"({len(callee)} bytes)", file=sys.stderr)
        if room == 0:
            print("dynld: account table too small for callee prefetch "
                  f"(max_accounts={A})", file=sys.stderr)
        return out
    if getattr(args, "artifact", None):
        from ..solidity import get_contracts_from_standard_json

        contracts = get_contracts_from_standard_json(
            args.artifact, getattr(args, "solc_input", None))
        if not contracts:
            print("error: artifact holds no deployed bytecode", file=sys.stderr)
            raise SystemExit(2)
        return contracts
    if args.code:
        return [MythrilDisassembler.load_from_bytecode(args.code, name=args.name)]
    if args.codefile:
        if args.codefile.endswith(".sol"):
            # reference: `myth analyze contract.sol` (SURVEY §3.1) —
            # requires a solc on PATH (or $MYTHRIL_SOLC)
            from ..solidity import SolcError, SolcNotFound

            try:
                contracts = MythrilDisassembler.load_from_solidity(
                    args.codefile)
            except (SolcNotFound, SolcError) as e:
                print(f"error: {e}", file=sys.stderr)
                raise SystemExit(2)
            if not contracts:
                print("error: no deployed bytecode compiled", file=sys.stderr)
                raise SystemExit(2)
            return contracts
        return [MythrilDisassembler.load_from_file(
            args.codefile, creation_path=args.creation_code, name=args.name)]
    print("error: provide bytecode via -c/--code, -f/--codefile, or --artifact",
          file=sys.stderr)
    raise SystemExit(2)


def _discover_plugins(plugin_dir):
    """Outer plugin discovery (entry points + optional directory); errors
    warn on stderr rather than aborting the analysis."""
    from ..plugin import discover

    disc = discover(plugin_dir=plugin_dir)
    for name, err in disc.errors.items():
        print(f"warning: plugin {name}: {err}", file=sys.stderr)
    return disc.laser_plugins


def exec_analyze(args) -> int:
    if args.concrete_storage and args.unconstrained_storage:
        print("error: --concrete-storage conflicts with "
              "--unconstrained-storage", file=sys.stderr)
        raise SystemExit(2)
    # telemetry spine (docs/observability.md): configure the process
    # tracer / metrics registry BEFORE the engine loads, finalize on
    # every exit path — a crashed run still leaves the JSONL prefix and
    # a best-effort metrics snapshot behind. obs imports are stdlib-only
    # so this stays safe pre-backend-probe.
    from ..obs import metrics as obs_metrics
    from ..obs import trace as obs_trace

    if getattr(args, "trace", None):
        obs_trace.configure(args.trace)
    if getattr(args, "metrics", None):
        obs_metrics.REGISTRY.enabled = True
    try:
        # CLI ingestion point: the whole analyze invocation is one
        # request trace — every span/event it emits (including fleet
        # units fed to other hosts) carries this id
        with obs_trace.trace_context():
            return _exec_analyze_inner(args)
    finally:
        # best-effort: a failed telemetry flush (unwritable dir, full
        # disk) must not mask the analysis result or its exception
        if getattr(args, "trace", None):
            try:
                obs_trace.close()
            except Exception as exc:
                print(f"warning: trace write failed: {exc}",
                      file=sys.stderr)
        if getattr(args, "metrics", None):
            try:
                obs_metrics.REGISTRY.write(args.metrics)
            except Exception as exc:
                print(f"warning: metrics write failed: {exc}",
                      file=sys.stderr)


def _exec_analyze_inner(args) -> int:
    # campaign mode dispatches BEFORE any engine import: --init-timeout
    # must be able to probe (and fall back from) a wedged backend while
    # this process is still backend-free. --fleet-follow is a campaign
    # with no local corpus (the feed ledger supplies the bytecode).
    if getattr(args, "corpus", None) or (
            getattr(args, "fleet", None)
            and getattr(args, "fleet_follow", False)):
        return _exec_campaign(args)
    if getattr(args, "fleet_follow", False):
        print("error: --fleet-follow requires --fleet DIR",
              file=sys.stderr)
        raise SystemExit(2)

    import dataclasses

    from ..mythril import MythrilAnalyzer, MythrilConfig
    from ..symbolic import SymSpec
    if getattr(args, "solver_store", None) and not args.no_solver_store:
        # single-shot analyze can still read/feed a shared verdict
        # store (e.g. the one a nightly campaign maintains)
        from ..smt import portfolio as smt_portfolio

        smt_portfolio.set_store(args.solver_store)
    contracts = _load_contracts(args)
    if args.code and args.creation_code:
        with open(args.creation_code) as fh:
            from ..disassembler.disassembly import _to_bytes

            contracts[0] = dataclasses.replace(
                contracts[0], creation_code=_to_bytes(fh.read()))
    cfg = MythrilConfig(
        limits=_limits_for(args),
        transaction_count=args.transaction_count,
        # --max-depth is the reference name for the per-path depth budget;
        # on the breadth-first frontier that IS the superstep budget
        max_steps=(args.max_depth if args.max_depth is not None
                   else args.max_steps),
        lanes_per_contract=args.lanes_per_contract,
        solver_iters=args.solver_iters,
        solver_timeout=(args.solver_timeout / 1000.0
                        if args.solver_timeout is not None else None),
        parallel_solving=args.parallel_solving,
        loop_bound=args.loop_bound,
        execution_timeout=args.execution_timeout,
        create_timeout=args.create_timeout,
        strategy=args.strategy,
        spec=SymSpec(storage=not args.concrete_storage),
        enable_iprof=args.enable_iprof,
        plugins=tuple(_discover_plugins(args.plugin_dir)),
    )
    if getattr(args, "rpc", None) and not getattr(
            args, "no_onchain_callees", False):
        # mid-execution dynamic loading (reference DynLoader.dynld ⚠unv):
        # runtime-computed call targets the PUSH20 pre-pass cannot see
        # are fetched at tx seams and resolve in the following tx;
        # reuse the -a path's client when one exists
        dl = getattr(args, "_dynld", None)
        if dl is None:
            from ..utils.loader import DynLoader, rpc_client_from_uri

            dl = DynLoader(rpc_client_from_uri(args.rpc))
        cfg = dataclasses.replace(cfg, dyn_loader=dl)
    analyzer = MythrilAnalyzer(contracts, cfg)
    modules = args.modules.split(",") if args.modules else None
    report = analyzer.fire_lasers(modules=modules)
    if args.graph:
        _write_graph(args.graph, contracts[0], analyzer)
    if args.statespace_json:
        _write_statespace(args.statespace_json, analyzer)
    if args.outform == "json":
        print(report.as_json())
    elif args.outform == "jsonv2":
        print(report.as_jsonv2())
    elif args.outform == "markdown":
        print(report.as_markdown())
    else:
        print(report.as_text())
    if args.enable_iprof:
        # separate channel, like the reference's profiler dump: the report
        # formats stay schema-stable whether or not profiling is on
        print(analyzer.sym.iprof_table(), file=sys.stderr)
    return 0


def _resolve_hosts(args):
    """(num_hosts, host_index) for campaign sharding: explicit flags win;
    an initialized jax.distributed runtime supplies pod defaults; a lone
    process is host 0 of 1."""
    n, i = args.num_hosts, args.host_index
    if n <= 0 or i < 0:
        import jax

        # ask the distributed runtime, not jax.process_count(): that
        # would initialize a backend, and a supervising parent must
        # leave the accelerator to its engine worker
        if jax.distributed.is_initialized() and jax.process_count() > 1:
            n = n if n > 0 else jax.process_count()
            i = i if i >= 0 else jax.process_index()
    n = n if n > 0 else 1
    i = i if i >= 0 else 0
    return n, i


def exec_campaign_merge(args) -> int:
    """Combine per-host campaign JSONs and/or fleet ledger dirs
    (reference has no analog — corpus scale is this rebuild's north
    star; SURVEY §5.8 corpus sharding, docs/fleet.md exactly-once
    merge). A missing or malformed input is a one-line typed error and
    a clean nonzero exit, never a traceback — merge runs on operator
    laptops against files scp'd off a pod."""
    import json
    import os

    from ..mythril.campaign import merge_campaigns

    results = []
    for p in args.results:
        if os.path.isdir(p):
            from ..fleet import ledger_results

            try:
                results.extend(ledger_results(p))
            except ValueError as e:
                print(f"error: campaign-merge: {e}", file=sys.stderr)
                return 2
            continue
        try:
            with open(p) as fh:
                doc = json.load(fh)
        except OSError as e:
            print(f"error: campaign-merge: cannot read {p}: "
                  f"{e.strerror or e}", file=sys.stderr)
            return 2
        except ValueError as e:
            print(f"error: campaign-merge: {p} is not valid JSON ({e})",
                  file=sys.stderr)
            return 2
        if not isinstance(doc, dict):
            print(f"error: campaign-merge: {p}: expected a campaign "
                  "result object", file=sys.stderr)
            return 2
        results.append(doc)
    merged = merge_campaigns(results)
    print(json.dumps(merged, indent=1))
    if args.strict_coverage:
        cov = merged.get("coverage")
        if cov is None:
            print("error: campaign-merge: --strict-coverage needs fleet "
                  "results (no coverage manifest in the inputs)",
                  file=sys.stderr)
            return 2
        if not cov.get("full"):
            print("error: campaign-merge: coverage incomplete: "
                  f"{cov.get('analyzed', 0)} analyzed + "
                  f"{cov.get('quarantined', 0)} quarantined of "
                  f"{cov.get('contracts', 0)} contracts "
                  f"({cov.get('lost', 0)} lost, "
                  f"{cov.get('unaccounted', 0)} unaccounted)",
                  file=sys.stderr)
            return 3
    return 0


def _exec_campaign(args) -> int:
    """Corpus campaign: BASELINE configs 2-3 (SURVEY §6), supervised by
    the resilience layer (watchdog + quarantine + backend fallback)."""
    import json
    import os

    from ..backend import parse_tiers
    from ..config import DEFAULT_RESILIENCE
    from ..resilience import BackendManager, FaultInjector, parse_ladder

    try:
        oom_ladder = parse_ladder(args.oom_ladder)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)

    # backend probe FIRST, while this process is still backend-free: a
    # wedged TPU runtime hangs jax.devices() forever; the probe wedges a subprocess instead, and
    # the campaign degrades to the CPU backend with the event on record
    try:
        backend_tiers = (parse_tiers(args.backend_tiers)
                         if args.backend_tiers else None)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)

    backend = None
    if args.init_timeout is not None:
        backend = BackendManager(
            init_timeout=args.init_timeout,
            max_attempts=DEFAULT_RESILIENCE.probe_attempts,
            backoff=DEFAULT_RESILIENCE.probe_backoff)
        ok, diag = backend.ensure_or_fallback(tiers=backend_tiers)
        if not ok:
            landed = os.environ.get("JAX_PLATFORMS", "cpu")
            print(f"warning: backend unavailable ({diag}); continuing "
                  f"on the {landed} backend", file=sys.stderr)

    from ..mythril.campaign import CorpusCampaign, load_corpus_dir
    from ..symbolic import SymSpec

    for flag, val in (("--create-timeout", args.create_timeout),
                      ("--statespace-json", args.statespace_json)):
        if val is not None:
            print(f"warning: {flag} has no effect in campaign mode",
                  file=sys.stderr)
    fleet_follow = getattr(args, "fleet_follow", False)
    if fleet_follow and args.corpus:
        print("error: --fleet-follow takes its contracts from the feed "
              "ledger; drop --corpus (or drop --fleet-follow for a "
              "static fleet)", file=sys.stderr)
        raise SystemExit(2)
    contracts = [] if fleet_follow else load_corpus_dir(
        args.corpus, max_members=_limits_for(args).max_accounts - 2)
    if args.fleet:
        # the ledger IS the work distribution: every worker sees the
        # whole corpus and claims leased units (docs/fleet.md); a
        # static strided split underneath would desync the manifest
        if args.num_hosts > 0 or args.host_index >= 0:
            print("warning: --num-hosts/--host-index are ignored with "
                  "--fleet (the ledger distributes the work)",
                  file=sys.stderr)
        if args.checkpoint_dir:
            print("warning: --checkpoint-dir is unused with --fleet "
                  "(per-unit result files are the durable record)",
                  file=sys.stderr)
        num_hosts, host_index = 1, 0
    else:
        num_hosts, host_index = _resolve_hosts(args)
    campaign = CorpusCampaign(
        contracts,
        batch_size=args.batch_size,
        lanes_per_contract=args.lanes_per_contract,
        limits=_limits_for(args),
        spec=SymSpec(storage=not args.concrete_storage),
        max_steps=(args.max_depth if args.max_depth is not None
                   else args.max_steps),
        solver_timeout=(args.solver_timeout / 1000.0
                        if args.solver_timeout is not None else None),
        solver_iters=args.solver_iters,
        parallel_solving=args.parallel_solving,
        transaction_count=args.transaction_count,
        modules=args.modules.split(",") if args.modules else None,
        checkpoint_dir=args.checkpoint_dir,
        execution_timeout=args.execution_timeout,
        plugins=tuple(_discover_plugins(args.plugin_dir)),
        enable_iprof=args.enable_iprof,
        num_hosts=num_hosts,
        host_index=host_index,
        batch_timeout=args.batch_timeout,
        max_batch_retries=args.max_batch_retries,
        fault_injector=FaultInjector.from_string(args.fault_inject),
        backend=backend,
        oom_ladder=oom_ladder,
        checkpoint_every=args.checkpoint_every,
        heartbeat_every=args.heartbeat,
        pipeline=args.pipeline,
        solver_workers=args.solver_workers,
        fleet_dir=args.fleet,
        lease_ttl=args.lease_ttl,
        unit_size=args.unit_size,
        max_unit_leases=args.max_unit_leases,
        worker_id=args.worker_id,
        fleet_follow=fleet_follow,
        # "auto" lets the campaign apply the fleet default
        # (<fleet-dir>/solver_store); --no-solver-store beats both
        solver_store=(None if args.no_solver_store
                      else (args.solver_store or "auto")),
        worker_isolation=args.worker_isolation,
        backend_tiers=backend_tiers,
    )

    unit_word = "unit" if args.fleet else "batch"

    def progress(done, total, dt, n_issues):
        print(f"{unit_word} {done}/{total}: {dt:.1f}s, {n_issues} "
              "issue(s) so far", file=sys.stderr)

    res = campaign.run(progress=progress)
    out = res.as_dict()
    if args.outform in ("json", "jsonv2"):
        out["issues_detail"] = res.issues
    print(json.dumps(out, indent=1))
    return 0


def exec_serve(args) -> int:
    """Always-on analysis daemon (docs/serving.md): admission queue +
    bytecode-hash dedupe + warm-compile reuse + streaming results over
    a thin stdlib HTTP surface. Blocks until SIGTERM/SIGINT completes
    the graceful drain."""
    from ..obs import metrics as obs_metrics
    from ..obs import trace as obs_trace
    from ..resilience import parse_ladder
    from ..serve import (AnalysisDaemon, ServeOptions, ShedPolicy,
                         TenantQuota)

    try:
        oom_ladder = parse_ladder(args.oom_ladder)
        if args.backend_tiers:
            from ..backend import parse_tiers

            parse_tiers(args.backend_tiers)  # fail fast on unknown tiers
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)
    default_quota = None
    if (args.tenant_rate is not None or args.tenant_burst is not None
            or args.tenant_max_inflight is not None):
        default_quota = TenantQuota(
            rate=args.tenant_rate, burst=args.tenant_burst,
            max_inflight=args.tenant_max_inflight)
    quotas = {}
    for spec in args.quota or []:
        tenant, sep, rest = spec.partition("=")
        if not sep or not tenant:
            print(f"error: bad --quota {spec!r}; want "
                  "TENANT=RATE[:BURST[:INFLIGHT]]", file=sys.stderr)
            raise SystemExit(2)
        try:
            quotas[tenant] = TenantQuota.parse(rest)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            raise SystemExit(2)
    shed = (None if args.no_shed
            else ShedPolicy(depth_hi=args.shed_depth_hi,
                            age_hi=args.shed_age_hi,
                            priority_max=args.shed_priority_max))
    if args.trace:
        obs_trace.configure(args.trace)
    opts = ServeOptions(
        batch_size=args.batch_size,
        lanes_per_contract=args.lanes_per_contract,
        max_steps=args.max_steps,
        transaction_count=args.transaction_count,
        modules=args.modules.split(",") if args.modules else None,
        limits_profile=args.limits_profile,
        solver_iters=args.solver_iters,
        solver_timeout=(args.solver_timeout / 1000.0
                        if args.solver_timeout is not None else None),
        solver_workers=args.solver_workers,
        batch_timeout=args.batch_timeout,
        max_batch_retries=args.max_batch_retries,
        oom_ladder=oom_ladder,
        fault_inject=args.fault_inject,
        concrete_storage=args.concrete_storage,
        worker_isolation=args.worker_isolation,
        backend_tiers=args.backend_tiers,
    )
    daemon = AnalysisDaemon(
        opts, data_dir=args.data_dir, host=args.host, port=args.port,
        dedupe=args.dedupe, max_queue=args.max_queue,
        drain_timeout=args.drain_timeout, fleet_dir=args.fleet,
        solver_store=(None if args.no_solver_store
                      else (args.solver_store or "auto")),
        quotas=quotas or None, default_quota=default_quota, shed=shed,
        follow_uri=args.follow, follow_poll=args.follow_poll,
        backfill_uri=args.backfill,
        backfill_window=args.backfill_window,
        compact_every=args.compact_every,
        store_only=args.store_only,
        compile_store=(args.compile_store or "auto"),
        prewarm=args.prewarm)
    daemon.install_signal_handlers()
    try:
        daemon.start()
        print(f"serving on {daemon.host}:{daemon.port} "
              f"(data dir {args.data_dir}"
              + (f", fleet feed {args.fleet}" if args.fleet else "")
              + ")", file=sys.stderr, flush=True)
        if args.port_file:
            with open(args.port_file, "w") as fh:
                fh.write(str(daemon.port))
        if args.heartbeat:
            _serve_heartbeat(daemon, args.heartbeat)
        daemon.wait_stopped()
    finally:
        daemon.shutdown("exit")
        if args.trace:
            try:
                obs_trace.close()
            except Exception as exc:  # noqa: BLE001 — never mask exit
                print(f"warning: trace write failed: {exc}",
                      file=sys.stderr)
        if args.metrics:
            try:
                obs_metrics.REGISTRY.write(args.metrics)
            except Exception as exc:  # noqa: BLE001
                print(f"warning: metrics write failed: {exc}",
                      file=sys.stderr)
    return 0


def _serve_heartbeat(daemon, period: float) -> None:
    """Start the serving heartbeat: one stderr line every ``period``
    seconds with queue depth, store size, and end-to-end request
    latency percentiles from the live ``serve_request_seconds``
    histogram (docs/observability.md "Heartbeat"). Daemon thread —
    dies with the process, never blocks drain."""
    import threading

    from ..obs import metrics as obs_metrics

    def _loop() -> None:
        while not daemon.wait_stopped(timeout=max(0.2, period)):
            rh = obs_metrics.REGISTRY.histogram(
                "serve_request_seconds",
                help="end-to-end request latency (submit to resolve)")
            rq = ""
            if rh.count:
                p50, p95 = rh.quantile(0.5), rh.quantile(0.95)
                rq = f" | req p50 {p50:.2f}s/p95 {p95:.2f}s"
            # compile-warmth token (docs/serving.md "Compile artifacts
            # & prewarm"): shape classes warm in-process / registry
            # buckets for the active tier
            wa = ""
            warm_a, warm_b = daemon.scheduler.warm_counts()
            if warm_a or warm_b:
                wa = f" warm {warm_a}/" + ("-" if warm_b is None
                                           else str(warm_b))
            print(f"[serve] depth {daemon.queue.depth()} "
                  f"store {daemon.store.count()}{wa}{rq}",
                  file=sys.stderr, flush=True)

    threading.Thread(target=_loop, daemon=True,
                     name="serve-heartbeat").start()


def _write_statespace(path: str, analyzer) -> None:
    """Explored-statespace JSON (reference: ``--statespace-json`` dumps
    the LASER node/edge graph, ``analysis/traceexplore.py`` ⚠unv). The
    frontier engine keeps no per-superstep node graph — its statespace IS
    the lane set — so the dump is per-transaction surviving paths (pc,
    frame depth, path-condition branches with their asserting pcs) plus
    per-contract instruction coverage, which carries the same audit
    content: what was reached, under which branch decisions."""
    import json

    import numpy as np

    sym = analyzer.sym
    out = {"transactions": [], "lanes": 0}
    for ti, ctx in enumerate(sym.tx_contexts):
        b = ctx.sf.base
        act = np.asarray(b.active)
        out["lanes"] = int(act.shape[0])
        pcs = np.asarray(b.pc)
        depth = np.asarray(b.depth)
        halted = np.asarray(b.halted)
        err = np.asarray(b.error)
        rev = np.asarray(b.reverted)
        cid = np.asarray(b.contract_id)
        con_pc = np.asarray(ctx.sf.con_pc)
        con_sign = np.asarray(ctx.sf.con_sign)
        con_len = np.asarray(ctx.sf.con_len)
        paths = []
        for lane in np.where(act)[0]:
            n = int(con_len[lane])
            paths.append({
                "lane": int(lane),
                "contract": ctx.cid_name(int(cid[lane])),
                "pc": int(pcs[lane]),
                "depth": int(depth[lane]),
                "halted": bool(halted[lane]),
                "error": bool(err[lane]),
                "reverted": bool(rev[lane]),
                "branches": [
                    {"pc": int(con_pc[lane, k]),
                     "taken": bool(con_sign[lane, k])}
                    for k in range(n) if int(con_pc[lane, k]) >= 0
                ],
            })
        out["transactions"].append({"tx": ti, "paths": paths})
    out["instruction_coverage_pct"] = sym.instruction_coverage()
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)


def _write_graph(path: str, contract, analyzer) -> None:
    """CFG of the first contract, explored blocks highlighted: a *.html
    path gets the self-contained interactive page (reference: the
    bundled-JS ``--graph`` HTML ⚠unv), anything else graphviz DOT."""
    from ..disassembler.cfg import CFG

    cfg = CFG(contract.code)
    sym = analyzer.sym
    if sym is not None and getattr(sym, "_visited", None) is not None:
        # runtime image index: with creation bytecodes the runtime images
        # occupy the second half of the corpus
        ci = len(sym.images) - len(analyzer.contracts)
        cfg.mark_reached(sym._visited[ci])
    render = (cfg.as_html if path.lower().endswith((".html", ".htm"))
              else cfg.as_dot)
    # explicit utf-8: the HTML template has non-ASCII (em dashes) and a
    # C-locale container would otherwise UnicodeEncodeError after the
    # whole symbolic run already succeeded
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(render(contract.name))


def exec_disassemble(args) -> int:
    contract = _load_contracts(args)[0]
    print(contract.get_easm(), end="")
    return 0


def exec_concolic(args) -> int:
    """Reference: ``myth concolic`` (``mythril/concolic`` ⚠unv) — here a
    front door over :func:`concolic_execution` (one sym_run serves every
    branch flip)."""
    import json

    from ..concolic import concolic_execution, load_concrete_data

    ja = ([int(x, 0) for x in args.jump_addresses.split(",")]
          if args.jump_addresses else None)
    caller = None
    if args.input:
        # reference trace-file mode (``myth concolic input.json`` ⚠unv);
        # the trace supplies code+seed, so explicit overrides conflict
        if args.calldata or args.code or args.codefile or args.callvalue:
            print("error: --input supplies code/calldata/value from the "
                  "trace; drop the conflicting flags", file=sys.stderr)
            raise SystemExit(2)
        code, calldata, callvalue, caller = load_concrete_data(args.input)
    else:
        if not args.calldata:
            print("error: provide --calldata or a --input trace file",
                  file=sys.stderr)
            raise SystemExit(2)
        contracts = _load_contracts(args)
        code = contracts[0].code
        calldata = bytes.fromhex(args.calldata.removeprefix("0x"))
        callvalue = args.callvalue
    flips = concolic_execution(
        code,
        calldata,
        jump_addresses=ja,
        callvalue=callvalue,
        caller=caller,
        limits=_limits_for(args),
        max_steps=args.max_steps,
        solver_iters=args.solver_iters,
    )
    print(json.dumps([
        {"pc": f.pc, "constraint_index": f.constraint_index,
         "calldata": "0x" + f.calldata.hex(),
         "callvalue": f.callvalue, "caller": f"0x{f.caller:040x}"}
        for f in flips
    ], indent=1))
    return 0


def exec_read_storage(args) -> int:
    from ..utils.loader import DynLoader, rpc_client_from_uri

    dl = DynLoader(rpc_client_from_uri(args.rpc))
    word = dl.read_storage(int(args.address, 16), int(args.index, 0))
    print(f"0x{word:064x}")
    return 0


def exec_function_to_hash(args) -> int:
    from ..utils.signatures import selector_of

    print("0x" + selector_of(args.signature))
    return 0


def _checksum_address(addr20: bytes) -> str:
    """EIP-55 mixed-case checksum encoding."""
    from ..ops.keccak import keccak256_host

    hexaddr = addr20.hex()
    h = keccak256_host(hexaddr.encode()).hex()
    return "0x" + "".join(
        ch.upper() if ch.isalpha() and int(h[i], 16) >= 8 else ch
        for i, ch in enumerate(hexaddr)
    )


def exec_hash_to_address(args) -> int:
    """Reference: ``myth hash-to-address`` — a 32-byte storage word whose
    low 20 bytes are an address, rendered checksummed (⚠unv)."""
    for word in args.hashes:
        raw = bytes.fromhex(word.removeprefix("0x").rjust(64, "0"))
        print(_checksum_address(raw[12:]))
    return 0


def exec_safe_functions(args) -> int:
    """Reference: ``myth safe-functions`` — functions in which no issue
    was detected (⚠unv). Coverage warnings are printed alongside: a
    function is only as safe as the exploration was complete."""
    from ..mythril import MythrilAnalyzer, MythrilConfig
    from ..utils.signatures import SignatureDB

    contracts = _load_contracts(args)
    cfg = MythrilConfig(
        limits=_limits_for(args),
        transaction_count=args.transaction_count,
        max_steps=args.max_steps,
        lanes_per_contract=args.lanes_per_contract,
    )
    analyzer = MythrilAnalyzer(contracts, cfg)
    report = analyzer.fire_lasers()
    flagged = {i.function for i in report.issues if i.function}
    db = SignatureDB()
    for contract in contracts:
        names = []
        for sel in contract.disassembly.func_hashes:
            sigs = db.lookup(sel)
            # same fallback name _label_functions gives issues, so an
            # unknown-selector function with findings is never "safe"
            name = sigs[0] if sigs else "0x" + sel.removeprefix("0x")
            if name not in flagged:
                names.append(name)
        print(f"{contract.name}: {len(names)} safe function(s)")
        for n in sorted(names):
            print(f"  {n}")
    for w in report.coverage_warnings():
        print(f"warning: {w}", file=sys.stderr)
    return 0


def exec_list_detectors(args) -> int:
    from ..analysis import ModuleLoader

    _discover_plugins(getattr(args, "plugin_dir", None))
    for m in ModuleLoader().get_detection_modules():
        print(f"{m.name} (SWC-{m.swc_id}): {m.description}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = create_parser()
    args = parser.parse_args(argv)
    if args.command in ("analyze", "a"):
        return exec_analyze(args)
    if args.command in ("disassemble", "d"):
        return exec_disassemble(args)
    if args.command == "concolic":
        return exec_concolic(args)
    if args.command == "read-storage":
        return exec_read_storage(args)
    if args.command == "function-to-hash":
        return exec_function_to_hash(args)
    if args.command == "hash-to-address":
        return exec_hash_to_address(args)
    if args.command == "safe-functions":
        return exec_safe_functions(args)
    if args.command == "campaign-merge":
        return exec_campaign_merge(args)
    if args.command == "serve":
        return exec_serve(args)
    if args.command == "list-detectors":
        return exec_list_detectors(args)
    if args.command == "version":
        from .. import __version__

        print(f"mythril_tpu {__version__}")
        return 0
    parser.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
