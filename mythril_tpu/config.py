"""Static-shape configuration for the device interpreter.

The reference has no analog — Python objects grow unboundedly
(``MachineState.stack`` is a list, memory a lazy dict ⚠unv, SURVEY.md §2
"State model"). On TPU every dimension is static; these caps define the
frontier array shapes. Lanes that exceed a cap raise a per-lane error flag
(masked trap) rather than crashing the batch — SURVEY.md §5.2.
"""

from __future__ import annotations

from dataclasses import dataclass

from .backend import default_oom_ladder


@dataclass(frozen=True)
class LimitsConfig:
    """Shape caps for one frontier. All sizes static at trace time."""

    max_stack: int = 256  # EVM allows 1024; solc output stays far below —
    # deep real-world frames trip ~30-60; 256 leaves 4x headroom and any
    # trip is attributed in the report coverage block (Trap.STACK)
    mem_bytes: int = 4096  # byte-addressable memory cap per lane
    calldata_bytes: int = 256  # symbolic tx calldata cap
    returndata_bytes: int = 256
    storage_slots: int = 64  # associative storage-cache entries per lane
    max_accounts: int = 8  # per-lane world-state account slots
    max_code: int = 24576  # EIP-170 runtime-code limit
    max_hash_bytes: int = 200  # SHA3 input cap (mapping keys are 64 bytes)
    log_slots: int = 8  # recorded LOG entries per lane
    tape_len: int = 512  # symbolic SSA tape nodes per lane
    max_constraints: int = 128  # path-condition slots per lane
    call_depth: int = 4  # saved call contexts per lane
    init_code_bytes: int = 1024  # in-tx CREATE/CREATE2 init-code buffer per
    # lane (longer init code falls back to the codeless-account path)
    call_log: int = 16  # recorded external-call events per lane
    arith_log: int = 32  # recorded symbolic-arithmetic events per lane
    propagate_every: int = 8  # supersteps between feasibility sweeps
    loop_bound: int = 8  # max taken backward jumps to one target per lane
    # (0 disables; reference: BoundedLoopsStrategy --loop-bound ⚠unv)
    loop_slots: int = 8  # tracked distinct back-jump targets per lane
    gas_schedule: str = "istanbul"  # "istanbul" (reference-era static
    # table) or "berlin" (EIP-2929 warm/cold access accounting)

    def __post_init__(self):
        assert self.max_stack >= 17  # SWAP16 arity
        assert self.mem_bytes % 32 == 0


DEFAULT_LIMITS = LimitsConfig()


@dataclass(frozen=True)
class ResilienceConfig:
    """Campaign-supervisor knobs (see ``mythril_tpu/resilience.py``).

    ``batch_timeout=None`` disables the per-batch watchdog (an
    interactive single-contract analyze has ``--execution-timeout`` for
    pacing; the watchdog exists for unattended corpus campaigns).
    ``init_timeout`` bounds the subprocess backend probe — 75 s
    comfortably covers a healthy TPU init (~20 s measured) while a
    wedged runtime hangs forever."""

    batch_timeout: float | None = None  # seconds per campaign batch
    init_timeout: float = 75.0          # seconds per backend-init probe
    max_batch_retries: int = 1          # re-attempts before bisection
    probe_attempts: int = 2             # backend re-init attempts
    probe_backoff: float = 5.0          # seconds between probe attempts
    # RESOURCE_EXHAUSTED degradation ladder, walked in order and
    # cumulatively (see resilience.DEGRADE_RUNGS / docs/resilience.md):
    # shrink the work until the batch fits instead of aborting the run.
    # The shape comes from the BackendProfile registry; the terminal
    # rung means "demote to the next available tier", not "pin to CPU"
    oom_ladder: tuple = default_oom_ladder()
    # batches between durable campaign-checkpoint writes (1 = every
    # batch — kill -9 at any instant loses at most one batch; larger
    # values trade replayed batches for less checkpoint I/O)
    checkpoint_every: int = 1
    # --- backend tiers (mythril_tpu/backend.py, docs/resilience.md
    # "Backend tiers"): the demote-and-repromote failover ladder
    backend_tiers: tuple | None = None   # ranked tier names; None = detect
    tier_probe_every: float = 30.0       # s between re-promotion probes
    tier_sticky_window: float = 20.0     # s a fresh demotion must hold
    tier_flap_window: float = 120.0      # rolling window for flap damping
    tier_flap_max: int = 4               # max transitions per flap window


DEFAULT_RESILIENCE = ResilienceConfig()

# Small limits for fast unit tests
TEST_LIMITS = LimitsConfig(
    max_stack=32,
    mem_bytes=1024,
    calldata_bytes=128,
    returndata_bytes=128,
    storage_slots=16,
    max_accounts=4,
    max_code=512,
    max_hash_bytes=136,
    log_slots=4,
    tape_len=128,
    max_constraints=32,
    call_depth=2,
    init_code_bytes=256,
    call_log=4,
    arith_log=8,
    propagate_every=4,
    loop_bound=4,
    loop_slots=4,
)
