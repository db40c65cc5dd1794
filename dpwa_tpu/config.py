"""Reference-compatible YAML configuration.

The reference (zenghanfu/dpwa) is driven by a YAML file whose ``nodes:`` list
enumerates the peer topology as ``{name, host, port}`` entries, plus protocol
knobs (fetch probability, socket timeout) and an interpolation spec
(SURVEY.md §2 "Config system"; reference file ``dpwa/config.py`` — mount empty,
reconstructed per SURVEY.md §0).  Contract preserved here (BASELINE.json:5):
**the same YAML file drives either transport** — the TCP transport uses
``host``/``port`` per node, while the ICI transport reinterprets the length of
``nodes:`` as the size of a device-mesh axis and ignores host/port.

Schema::

    nodes:
      - {name: node0, host: 127.0.0.1, port: 45000}
      - {name: node1, host: 127.0.0.1, port: 45001}
    protocol:
      schedule: ring            # ring | random | hierarchical | exponential
      mode: pairwise            # pairwise (mutual merge) | pull (one-sided)
      fetch_probability: 1.0    # per-step chance a pair actually exchanges
      timeout_ms: 500           # TCP transport only: fetch budget
                                #   (connect+header; payload earns
                                #   1s per min_wire_mb_per_s received)
      min_wire_mb_per_s: 10.0       # TCP only: slowest peer rate treated
                                #   as alive (deadline floor)
      seed: 0                   # schedule / participation RNG seed
      pool_size: null           # random schedule: # static pairings compiled
                                #   (default auto = clamp(2n, 16, 128))
      group_size: 0             # hierarchical: peers per host group (0 = auto)
      inter_period: 4           # hierarchical: cross-group exchange cadence
      drop_probability: 0.0     # fault injection: drop pairs at this rate
      wire_dtype: f32           # f32 | bf16 | int8 (shipped replica compressed)
      wire_codec: dense         # dense | topk (TCP only: topk ships only the
                                #   k largest-magnitude changed coordinates
                                #   against an error-feedback residual; see
                                #   docs/wire.md)
      topk_fraction: 0.05       # topk codec: k = round(fraction * n),
                                #   clamped to [1, n]
      topk_values: int8         # topk value block: int8 (chunk-scaled SR,
                                #   ~5 B/coord) | f32 (exact, 8 B/coord)
      overlap_prefetch: false   # TCP only: double-buffered pipeline — round
                                #   t+1's partner fetch streams while round
                                #   t's decode/screen/merge runs; payloads
                                #   that straddle a local publish re-screen
      rx_server: threaded       # threaded (thread-per-connection Rx) |
                                #   reactor (single-threaded selectors
                                #   event loop, docs/transport.md; wire
                                #   behavior identical, chaos still
                                #   forces the threaded server)
      async_rounds:             # barrier-free async gossip (TCP only,
                                #   docs/async.md); absent/off keeps the
                                #   lock-step round loop byte-identical
        enabled: false          # decouple publish from merge: frames land
                                #   in per-peer queues and merge when ready
        max_staleness: 4        # largest publish-clock lag still merged;
                                #   beyond it the frame drops as the soft
                                #   ``stale`` outcome (degrade, never
                                #   quarantine)
        staleness_damping: 0.5  # per-lag alpha decay: a frame lagging L
                                #   clocks merges at alpha * damping**L,
                                #   composing with trust damping
        queue_depth: 4          # bounded per-peer pending queue (newest
                                #   frames win admission)
        fold: true              # batch pending dense frames through one
                                #   exchange_on_device_fold dispatch
    shard:                      # sharded gossip (TCP only, docs/wire.md)
      k: 1                      # contiguous shards per replica; each round
                                #   ships ONE shard (k× fewer wire bytes,
                                #   full coverage every k rounds), merged
                                #   slice-wise.  1 = off: frames stay
                                #   byte-identical to a pre-shard build
    interpolation:
      type: constant            # constant | clock | loss
      factor: 0.5               # constant alpha (0.5 == (local+remote)/2)
    health:                     # peer-health control plane (TCP transport)
      enabled: true             # failure detection + quarantine/remap
      suspicion_threshold: 2.0  # quarantine when suspicion crosses this
      ewma_alpha: 0.2           # latency/throughput EWMA smoothing
      success_decay: 0.25       # suspicion multiplier per good fetch
      quarantine_base_rounds: 4 # first quarantine length (doubles per
                                #   consecutive failed probe, clamped)
      quarantine_max_rounds: 64
      jitter_rounds: 2          # deterministic backoff jitter in [0, j]
      probe_timeout_ms: 100     # header-only re-admission probe budget
      healthz_port: null        # JSON /healthz endpoint (null = off,
                                #   0 = OS-assigned port)
    chaos:                      # deterministic fault injection harness
      enabled: false            # forces the Python Rx server when on
      seed: 0
      drop_probability: 0.0     # close the connection before serving
      delay_probability: 0.0    # sleep delay_ms before serving
      delay_ms: 50.0
      throttle_probability: 0.0 # serve at throttle_bytes_per_s
      throttle_bytes_per_s: 1e6
      truncate_probability: 0.0 # cut the frame mid-payload
      corrupt_probability: 0.0  # flip the frame's magic bytes
      down_windows: []          # [{peer, start, stop}]: hard-down rounds
      partition_windows: []     # [{group: [peers], start, stop}]: block all
                                #   links between group and its complement
      link_windows: []          # [{src, dst, start, stop}]: block one
                                #   DIRECTED link (asymmetric faults)
      partition_probability: 0.0  # drawn partitions: each block of
                                #   partition_len_rounds splits the ring
                                #   into two drawn groups at this rate
      partition_len_rounds: 8
      byzantine_peers: []       # peers eligible for byzantine injection
                                #   ([] = all peers)
      byzantine_start_round: 0  # rounds before this serve honestly
      byzantine_sign_probability: 0.0   # serve the sign-flipped replica
      byzantine_scale_probability: 0.0  # serve a scaled replica (finite,
                                #   below recovery.max_param_norm)
      byzantine_scale_factor: 100.0
      byzantine_replay_probability: 0.0 # re-serve an old own snapshot
      byzantine_replay_age: 8   # how many rounds stale the replay is
      byzantine_zero_probability: 0.0   # serve an all-zero replica
      trickle_windows: []       # [{peer, start, stop}]: serve at
                                #   trickle_bytes_per_s (straggler shaping)
      trickle_bytes_per_s: 2048.0
      stall_probability: 0.0    # jittered mid-payload serving stall
      stall_ms_max: 200.0       # drawn stall length in [0, stall_ms_max]
      accept_delay_windows: []  # [{peer, start, stop}]: sleep before
                                #   reading the request (accept-path lag)
      accept_delay_ms: 100.0
      bandwidth_windows: []     # [{peer, start, stop}]: link-quality
                                #   flapping — time slices into blocks of
                                #   bandwidth_block_rounds rounds; each
                                #   block draws shaped-or-not (chaos kind
                                #   13) and, when shaped, a serving rate
                                #   in [bandwidth_bps_min, bps_max] (kind
                                #   14); composes with trickle windows by
                                #   taking the slower rate
      bandwidth_flap_probability: 1.0  # per-block chance the link flaps
      bandwidth_block_rounds: 4 # rounds per flap block (square-wave width)
      bandwidth_bps_min: 4096.0 # drawn shaped-rate range (bytes/s)
      bandwidth_bps_max: 65536.0
    recovery:                   # crash recovery & divergence guard
      enabled: true             # peer bootstrap serving + payload guard
      max_param_norm: 1.0e12    # reject/roll back when ||vec||_2 exceeds
      max_loss: 1.0e9           # reject/roll back when |loss| exceeds
      rescue_loss: null         # finite local loss beyond THIS bound gets
                                #   the interpolation alpha=1 rescue
                                #   (null = 16 * max_loss; must be >=
                                #   max_loss so a normal training spike
                                #   near the guard bound never triggers
                                #   wholesale replica adoption)
      min_param_norm_ratio: 1.0e-4  # reject a remote whose norm is below
                                #   this fraction of the local norm
                                #   (zero-energy payload; 0 = off)
      snapshot_every: 1         # push a last-good ring snapshot every k
                                #   healthy steps
      snapshot_ring: 4          # in-memory last-good snapshots kept
      state_chunk_bytes: 1048576  # STATE transfer chunk size (CRC per chunk)
      bootstrap_timeout_ms: 10000 # per-chunk fetch budget during bootstrap
      max_resume_retries: 8     # short-read resume attempts per bootstrap
      max_clock_lag: 64.0       # re-admission freshness: advise re-sync
                                #   when a readmitted peer's clock leads
                                #   ours by more than this
      auto_resync: false        # adapter re-bootstraps itself when a
                                #   re-admission freshness check trips
    membership:                 # epidemic membership & partition tolerance
      enabled: true             # piggyback a membership digest on every
                                #   gossip frame (needs health.enabled)
      indirect_probes: 2        # K relay probes before suspect->quarantine
      relay_timeout_ms: 250     # budget per relay probe round-trip
      dead_after_quarantines: 3 # declare a peer dead after this many
                                #   consecutive failed re-admissions
      dead_gossip_rounds: 16    # disseminate a dead claim this many
                                #   rounds, then EVICT the peer's
                                #   per-peer state (scoreboard, trust,
                                #   flowctl) and drop it from the digest
                                #   until it refutes (0 = never evict)
      quorum_fraction: 0.5      # degraded mode when the connected
                                #   component falls below this fraction
      degraded_alpha_scale: 1.0 # damp interpolation alpha while degraded
                                #   (1.0 = off)
      heal_reconcile: true      # anti-entropy state merge on partition heal
      reconcile_min_fraction: 0.3  # reconcile only when the returning
                                #   component is at least this fraction
      max_heal_weight: 0.75     # clamp on the returning side's merge weight
    trust:                      # content-trust plane (docs/trust.md)
      enabled: true             # screen every decoded REMOTE payload
      window: 32                # median/MAD window of accepted exchanges
      min_window: 8             # screening arms once this many accepted
                                #   exchanges exist (cold-start guard)
      mad_multiplier: 8.0       # robust z beyond this -> suspect (damped)
      reject_multiplier: 24.0   # robust z beyond this -> rejected
      damping: 1.0              # alpha *= trust ** damping for suspects
      ewma_half_life: 4.0       # clean exchanges to halve trust deficit
      suspect_decay: 0.7        # trust *= this per suspect verdict
      reject_decay: 0.25        # trust *= this per rejected verdict
      quarantine_trust: 0.15    # below this, feed 'untrusted' probes to
                                #   the scoreboard until quarantine
      cosine_floor: -0.5        # hard bound: reject anti-aligned payloads
      norm_ratio_max: 64.0      # hard bound: reject scale blow-ups
      replay_slack: 0.5         # clock may run backward by this much
                                #   before a payload counts as a replay
      amnesty_gap: 4            # a peer unscreened for amnesty_gap *
                                #   (n_peers - 1) rounds is re-acquainted
      amnesty_rounds: 8         # ...leniently for this many rounds
                                #   (rejects downgrade to damped suspects)
    flowctl:                    # flow control plane (docs/flowctl.md)
      enabled: true             # adaptive deadlines + serving admission
                                #   (forces the Python Rx server)
      quantile: 0.95            # per-peer latency quantile the budget
                                #   tracks (also the hedge launch point)
      margin: 1.5               # deadline = quantile latency * margin
      min_ms: 50.0              # adaptive-deadline clamp (floor)
      max_ms: 5000.0            # adaptive-deadline clamp (ceiling)
      window: 32                # success-latency samples kept per peer
      warmup: 5                 # cold below this many samples: fall back
                                #   to protocol.timeout_ms, never hedge
      hedge: true               # one hedged retry to the schedule's
                                #   fallback partner once the p95 lapses
      degrade_shed_fraction: 0.5  # fraction of rounds deterministically
                                #   remapped away from a DEGRADED partner
      max_connections: 32       # serving: global concurrent-conn cap
                                #   (threaded Rx: bounds worker threads)
      reactor_max_connections: 1024  # serving cap under rx_server:
                                #   reactor — a connection there costs a
                                #   registered socket, not a thread
      token_rate: 100.0         # serving: requests/s refill per remote
      token_burst: 200.0        # serving: token bucket depth per remote
      max_inflight_bytes: 268435456  # serving: payload bytes in flight
      min_ingest_bytes_per_s: 4096.0 # slow-loris eviction floor on
                                #   request reads
      request_timeout_ms: 5000  # per-connection handler budget (was the
                                #   hard-coded 5 s accept-path timeout)
      busy_retry_ms: 50         # retry hint carried in the DPWB reply
    obs:                        # observability plane (docs/observability.md)
      trace: true               # per-stage round spans + cross-peer trace
                                #   IDs piggybacked on frames (forces the
                                #   Python Rx server for serve-side spans)
      trace_every: 1            # sample 1-in-N rounds for tracing
      trace_path: trace.jsonl   # trace JSONL stream (null = in-memory only)
      trace_max_records: 4096   # in-memory trace ring (tests/adapters)
      sketch: true              # piggyback a replica sketch per frame for
                                #   the ring-disagreement estimate
      sketch_k: 64              # sketch width (floats on the wire)
      sketch_every: 1           # refresh the local sketch 1-in-N publishes
      metrics: true             # Prometheus /metrics on the healthz port
      log_max_bytes: 0          # rotate metrics/health JSONL at this size
                                #   (0 = unbounded)
      log_keep: 1               # rotated generations kept per JSONL file
                                #   (<path>.1 .. <path>.N)
      incidents: true           # online anomaly detectors + incident
                                #   correlator (docs/incidents.md) and the
                                #   /incidents healthz route
      incident_path: null       # alert/incident JSONL stream ("{me}" is
                                #   substituted; null = in-memory only)
      incident_window: 8        # rounds of evidence behind the burst and
                                #   storm detectors
      incident_fail_streak: 2   # consecutive hard fetch failures from one
                                #   peer before a peer_failure alert
      incident_soft_streak: 2   # busy/slow outcomes from one peer inside
                                #   the window before a straggler alert
      incident_trust_burst: 2   # untrusted/poisoned outcomes from one
                                #   peer inside the window before a
                                #   trust_burst alert
      incident_storm_threshold: 3  # quarantine/degrade transitions inside
                                #   the window before a state_storm alert
      incident_stale_storm: 3   # async bounded-staleness drops inside the
                                #   window before a staleness_storm alert
                                #   (docs/async.md)
      incident_stall_window: 8  # rel_rms samples behind the convergence
                                #   stall detector
      incident_stall_min_rel: 0.05  # plateau only counts above this
                                #   rel_rms floor (converged is not stalled)
      incident_stall_improve: 0.01  # required fractional rel_rms
                                #   improvement across the stall window
      incident_slo_factor: 4.0  # round wall beyond this multiple of the
                                #   rolling median starts an SLO burn
      incident_slo_rounds: 5    # consecutive burning rounds before an
                                #   slo_burn alert
      incident_slo_warmup: 16   # wall samples before the SLO baseline arms
      incident_resolve_after: 8 # quiet rounds (no evidence, implicated
                                #   peers healthy) before an incident
                                #   resolves
      recorder: true            # black-box flight recorder: bounded ring
                                #   of per-round records dumped on crash /
                                #   incident open / close / endpoint
      recorder_rounds: 64       # flight-recorder ring depth (rounds)
      recorder_path: flight-{me}.jsonl  # dump path ("{me}" substituted;
                                #   null = dpwa-flight-<me>.jsonl in cwd)
    topology:                   # hierarchical gossip (docs/hierarchy.md);
                                #   absent block = one flat ring,
                                #   bit-identical to pre-hierarchy builds
      islands:                  # partition of nodes: into islands — every
                                #   node in EXACTLY one island; each island
                                #   averages internally (ICI ppermute path)
                                #   and only its elected leader speaks on
                                #   the wide-area ring
        - name: rack0           # island id (defaults island<i>)
          nodes: [node0, node1] # member names from nodes:
        - name: rack1
          nodes: [node2, node3]
      leader_seed: 0            # threefry seed of the leader_draw stream
                                #   (election + failover succession)
      intra_rounds: 1           # intra-island averaging sweeps folded in
                                #   per wide-area round (hypercube phases;
                                #   1 sweep = exact island mean)
    run:                        # training-harness loop (docs/training.md)
      steps: 100                # optimizer steps per node
      batch_size: 32            # per-node minibatch size
      lr: 0.1                   # SGD learning rate
      momentum: 0.0             # SGD momentum (0 = plain SGD)
      loss_every: 1             # emit a loss record every k steps
      checkpoint_every: 0       # save a checkpoint every k steps (0 = off)
      checkpoint_dir: null      # checkpoint directory ("{me}" substituted)
      checkpoint_keep: 3        # newest checkpoints kept per node
      target_loss: 0.0          # time-to-loss threshold the acceptance
                                #   legs measure against (0 = off)
    tune:                       # self-tuning wire (docs/tune.md); absent
                                #   block or enabled: false keeps frames
                                #   byte-identical to a static-config build
      enabled: false            # per-link degradation controller: walks
                                #   the frozen codec ladder (f32 -> bf16 ->
                                #   int8 -> topk 0.1 -> 0.03 -> 0.01) from
                                #   the obs planes' QUANTIZED observations
      window: 8                 # observation rounds per link behind each
                                #   decision
      min_dwell_rounds: 6       # rounds a link holds a rung before it may
                                #   escalate again (hysteresis)
      cooldown_rounds: 12       # rounds after a back-off during which the
                                #   link may not re-escalate
      wire_bound_frac: 0.5      # quantized wire-span fraction of the round
                                #   wall at/above which a round counts as
                                #   wire-bound
      escalate_frac: 0.5        # fraction of the window's rounds that must
                                #   be wire-bound (or busy/slow/stale) to
                                #   escalate one rung
      stall_eps: 0.02           # minimum fractional rel_rms improvement
                                #   across the window; below it the sketch
                                #   plane reads "stalling" -> back off one
                                #   rung
      shed_rungs: 2             # extra rungs shed while the scheduled
                                #   partner is scoreboard-DEGRADED —
                                #   fidelity is shed, the round is NOT
                                #   dropped (replaces the degrade_shed
                                #   remap while enabled)
      quant: 16                 # quantization buckets for observed span
                                #   fractions and trends (decisions never
                                #   branch on raw wall-clock, so seeded
                                #   reruns replay bit-identically)
      jitter_rounds: 2          # threefry dwell jitter (tag 37): drawn
                                #   extra dwell in [0, j] desynchronizes
                                #   fleet-wide escalations
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import yaml


@dataclasses.dataclass(frozen=True)
class NodeSpec:
    """One ``nodes:`` entry: a peer's identity and (TCP-only) address."""

    name: str
    host: str = "127.0.0.1"
    port: int = 0


# One source of truth for the TCP liveness floor (MEGABYTES/s):
# ProtocolConfig's default and parallel/tcp.py's module default both
# derive from this, so the "same" default cannot drift between the
# config path and direct fetch_blob() calls.
DEFAULT_MIN_WIRE_MB_PER_S = 10.0


@dataclasses.dataclass(frozen=True)
class AsyncRoundsConfig:
    """``protocol.async_rounds`` block — barrier-free gossip rounds.

    Off (the default, and the absent-block case) keeps the lock-step
    round loop byte-identical to a pre-async build.  On, the
    :class:`~dpwa_tpu.parallel.async_loop.AsyncExchangeEngine` decouples
    publish from merge: frames stream on background slots, land in a
    bounded per-peer pending queue, and merge whenever ready instead of
    at the round barrier.  Each merge damps its interpolation weight by
    ``staleness_damping ** lag`` (lag = local step − the frame's publish
    clock), and a frame whose lag exceeds ``max_staleness`` is dropped
    as the soft ``stale`` outcome (degrade, never quarantine).  See
    docs/async.md."""

    enabled: bool = False
    # Largest publish-clock lag still merged.  Lag == max_staleness
    # merges (maximally damped); lag > max_staleness drops as ``stale``.
    max_staleness: int = 4
    # Per-lag alpha decay: a frame lagging L clocks merges at
    # alpha * staleness_damping**L, composing multiplicatively with the
    # trust damping already in interpolation._clamped.  1.0 disables
    # damping (bounded-staleness drops still apply).
    staleness_damping: float = 0.5
    # Bounded per-peer pending queue: admission keeps the newest
    # ``queue_depth`` frames per peer (older publish clocks are shed
    # first — they would merge at the smallest weight anyway).
    queue_depth: int = 4
    # Batch consecutive pending dense frames into one
    # exchange_on_device_fold dispatch (device substrate only; the host
    # substrate always folds sequentially, which is bit-identical).
    fold: bool = True

    def __post_init__(self) -> None:
        if self.max_staleness < 1:
            raise ValueError(
                f"async_rounds.max_staleness must be >= 1, "
                f"got {self.max_staleness}"
            )
        if not 0.0 < self.staleness_damping <= 1.0:
            raise ValueError(
                f"async_rounds.staleness_damping must be in (0, 1], "
                f"got {self.staleness_damping}"
            )
        if self.queue_depth < 1:
            raise ValueError(
                f"async_rounds.queue_depth must be >= 1, "
                f"got {self.queue_depth}"
            )


@dataclasses.dataclass(frozen=True)
class ProtocolConfig:
    schedule: str = "ring"
    mode: str = "pairwise"  # pairwise (mutual merge) | pull (one-sided)
    fetch_probability: float = 1.0
    timeout_ms: int = 500
    # TCP transport: the slowest transfer rate still treated as a live
    # peer, in MEGABYTES per second (the name says mb_per_s, not mbps,
    # deliberately — a megabit reading would be off by 8×).  The fetch
    # deadline is timeout_ms (connect + header) plus 1 / this rate per
    # payload byte RECEIVED, so large replicas are never rejected by a
    # fixed budget while trickling peers still die promptly.
    # Deployments on genuinely slow fabrics (WAN links below 10 MB/s)
    # with large models must lower this, or every large fetch is
    # abandoned and gossip silently degrades to solo training.
    min_wire_mb_per_s: float = DEFAULT_MIN_WIRE_MB_PER_S
    seed: int = 0
    # Random schedule: number of static matchings compiled into the
    # lax.switch pool.  None = auto-scale with the peer count,
    # clamp(2n, 16, 128): artifacts/pool_truncation.json shows mixing
    # time reaches the fresh-draw rate by K=16 but pair COVERAGE at
    # n=64/K=16 is only 23 % (3/4 of pairs could never meet), while the
    # switch's compile cost stays flat to K=128.  Explicit values are
    # honored unchanged (the TCP/host path pays no compile cost and can
    # go higher freely).
    pool_size: int | None = None
    group_size: int = 0
    inter_period: int = 4
    drop_probability: float = 0.0  # fault injection: drop pairs at this rate
    # Wire precision of the SHIPPED replica: "f32" (exact, the reference's
    # format) or "bf16" — halves exchange traffic (ICI/DCN bytes, TCP wire
    # bytes); the local replica and the merge arithmetic stay f32, only
    # the partner's contribution is rounded.  Pairwise-averaging tolerates
    # this well: quantization error enters scaled by alpha and is averaged
    # away across rounds.
    wire_dtype: str = "f32"
    # Wire CODEC of the shipped replica (TCP transport only).  "dense"
    # ships every coordinate at wire_dtype precision; "topk" ships only
    # the k = round(topk_fraction * n) largest-magnitude coordinates that
    # changed since the last publish (error-feedback residual scoring, so
    # dropped coordinates accumulate and ship later), as absolute values
    # the receiver splices into its OWN replica.  Orthogonal to
    # wire_dtype: topk_values picks the value-block precision.
    wire_codec: str = "dense"
    topk_fraction: float = 0.05
    topk_values: str = "int8"
    # TCP transport: double-buffered exchange pipeline.  When on, round
    # t+1's partner fetch (deadline-hedged as usual) streams on a
    # background slot while round t's decode -> trust-screen -> merge
    # runs; a prefetched payload that straddles a local publish is
    # re-screened against the fresh replica before merging.  Off by
    # default: the sequential path is the bit-identity reference.
    overlap_prefetch: bool = False
    # Which Rx server serves this node's published frames (TCP
    # transport).  "threaded" is the thread-per-connection PeerServer;
    # "reactor" is the single-threaded selectors event loop
    # (dpwa_tpu/parallel/reactor.py, docs/transport.md) whose admitted
    # connections cost a registered socket instead of a worker thread —
    # the large-N serving path.  Wire behavior is byte-identical.
    # chaos.enabled still forces the threaded chaos wrapper: fault
    # injection needs per-connection control of a blocking serve loop.
    rx_server: str = "threaded"
    # Barrier-free async rounds (docs/async.md): accepts the nested
    # AsyncRoundsConfig or the YAML-block mapping shorthand.  Disabled
    # by default — the lock-step round loop is the bit-identity
    # reference the async engine is tested against.
    async_rounds: "AsyncRoundsConfig | Mapping[str, Any]" = (
        dataclasses.field(default_factory=AsyncRoundsConfig)
    )

    def __post_init__(self) -> None:
        if isinstance(self.async_rounds, Mapping):
            # YAML-block shorthand: coerce in place (frozen dataclass,
            # same discipline as ChaosConfig's window normalization).
            object.__setattr__(
                self, "async_rounds", AsyncRoundsConfig(**self.async_rounds)
            )
        if not 0.0 <= self.fetch_probability <= 1.0:
            raise ValueError(
                f"fetch_probability must be in [0, 1], got {self.fetch_probability}"
            )
        if not 0.0 <= self.drop_probability <= 1.0:
            raise ValueError(
                f"drop_probability must be in [0, 1], got {self.drop_probability}"
            )
        if self.schedule not in (
            "ring", "random", "hierarchical", "exponential"
        ):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.mode not in ("pairwise", "pull"):
            raise ValueError(f"unknown protocol mode {self.mode!r}")
        if self.wire_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(f"unknown wire_dtype {self.wire_dtype!r}")
        if self.wire_codec not in ("dense", "topk"):
            raise ValueError(f"unknown wire_codec {self.wire_codec!r}")
        if not 0.0 < self.topk_fraction <= 1.0:
            raise ValueError(
                f"topk_fraction must be in (0, 1], got {self.topk_fraction}"
            )
        if self.topk_values not in ("int8", "f32"):
            raise ValueError(f"unknown topk_values {self.topk_values!r}")
        if self.min_wire_mb_per_s <= 0:
            raise ValueError(
                f"min_wire_mb_per_s must be > 0, got {self.min_wire_mb_per_s}"
            )
        if self.pool_size is not None and self.pool_size < 1:
            raise ValueError(
                f"pool_size must be >= 1 (or null for auto), "
                f"got {self.pool_size}"
            )
        if self.rx_server not in ("threaded", "reactor"):
            raise ValueError(f"unknown rx_server {self.rx_server!r}")

    def resolved_pool_size(self, n_peers: int) -> int:
        """The random-schedule pool size in effect for ``n_peers``."""
        if self.pool_size is not None:
            return self.pool_size
        return max(16, min(128, 2 * n_peers))


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """``shard:`` block — exchange 1/k of the replica per round.

    ``k: 1`` (the default) or an absent block keeps sharding OFF and
    every wire frame byte-identical to a pre-shard build.  ``k > 1``
    partitions the flattened replica into k contiguous shards; each
    publish ships the one shard the per-epoch ``shard_draw``
    permutation assigns to that round (every shard once per k rounds),
    and the merge touches only that slice.  Composes with
    ``protocol.wire_dtype`` / ``protocol.wire_codec`` — the inner
    encoding applies to the slice (top-k selects within the shard, int8
    scale tables restart per shard).  TCP transport only; see
    docs/wire.md."""

    k: int = 1

    def __post_init__(self) -> None:
        if self.k < 1:
            raise ValueError(f"shard.k must be >= 1, got {self.k}")

    @property
    def enabled(self) -> bool:
        return self.k > 1


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """``health:`` block — the peer-health control plane's knobs.

    Applies to the TCP transport (the path with per-peer fetches to
    fail); the SPMD transports emulate failures in-graph via
    ``protocol.drop_probability`` and need no detector.  Quarantine
    timing is counted in gossip ROUNDS, never wall time, so health state
    is deterministic for a fixed outcome sequence (see
    :mod:`dpwa_tpu.health.scoreboard`)."""

    enabled: bool = True
    # Quarantine when a peer's suspicion crosses this.  Failure weights
    # (detector.DEFAULT_FAILURE_WEIGHTS) are ~1 per hard failure, so the
    # default 2.0 means two consecutive hard failures.
    suspicion_threshold: float = 2.0
    ewma_alpha: float = 0.2
    success_decay: float = 0.25
    quarantine_base_rounds: int = 4
    quarantine_max_rounds: int = 64
    jitter_rounds: int = 2
    probe_timeout_ms: int = 100
    # None = no endpoint; 0 = OS-assigned port; >0 = fixed port.  The
    # endpoint serves the scoreboard snapshot as JSON over plain HTTP
    # (stdlib-only, dpwa_tpu/health/endpoint.py).
    healthz_port: int | None = None

    def __post_init__(self) -> None:
        if self.suspicion_threshold <= 0:
            raise ValueError(
                f"suspicion_threshold must be > 0, got {self.suspicion_threshold}"
            )
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError(
                f"ewma_alpha must be in (0, 1], got {self.ewma_alpha}"
            )
        if not 0.0 <= self.success_decay < 1.0:
            raise ValueError(
                f"success_decay must be in [0, 1), got {self.success_decay}"
            )
        if self.quarantine_base_rounds < 1:
            raise ValueError(
                f"quarantine_base_rounds must be >= 1, "
                f"got {self.quarantine_base_rounds}"
            )
        if self.quarantine_max_rounds < self.quarantine_base_rounds:
            raise ValueError(
                "quarantine_max_rounds must be >= quarantine_base_rounds"
            )
        if self.jitter_rounds < 0:
            raise ValueError(
                f"jitter_rounds must be >= 0, got {self.jitter_rounds}"
            )
        if self.probe_timeout_ms < 1:
            raise ValueError(
                f"probe_timeout_ms must be >= 1, got {self.probe_timeout_ms}"
            )
        if self.healthz_port is not None and not 0 <= self.healthz_port < 65536:
            raise ValueError(
                f"healthz_port must be in [0, 65535] or null, "
                f"got {self.healthz_port}"
            )


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """``chaos:`` block — deterministic fault injection for the TCP path.

    Faults are drawn per (seed, round, peer) on independent threefry
    streams (:func:`dpwa_tpu.parallel.schedules.chaos_draw`), so a given
    seed replays the identical fault schedule — the harness doubles as a
    soak tool (``chaos:`` in YAML) and a test fixture
    (:mod:`dpwa_tpu.health.chaos`).  ``down_windows`` hard-kills a peer's
    Rx serving for a round interval ``[start, stop)`` — the
    'process died, later came back' scenario quarantine/re-admission is
    proven against."""

    enabled: bool = False
    seed: int = 0
    drop_probability: float = 0.0
    delay_probability: float = 0.0
    delay_ms: float = 50.0
    throttle_probability: float = 0.0
    throttle_bytes_per_s: float = 1e6
    truncate_probability: float = 0.0
    corrupt_probability: float = 0.0
    down_windows: tuple[tuple[int, int, int], ...] = ()
    # Partition injection: during [start, stop) every link BETWEEN
    # ``group`` and its complement is blocked (both directions); links
    # inside either side stay up.  Entry shape: (group_tuple, start, stop)
    # or the YAML mapping {group: [...], start, stop}.
    partition_windows: tuple[tuple[tuple[int, ...], int, int], ...] = ()
    # Single DIRECTED link block (src cannot reach dst) — the asymmetric
    # fault that makes one node falsely suspect a live peer.  Entry shape:
    # (src, dst, start, stop) or {src, dst, start, stop}.
    link_windows: tuple[tuple[int, int, int, int], ...] = ()
    # Drawn partitions: time is sliced into blocks of partition_len_rounds
    # rounds; each block independently splits the ring at this rate, with
    # per-peer group assignment drawn per block (chaos_draw kinds 5/6).
    partition_probability: float = 0.0
    partition_len_rounds: int = 8
    # Byzantine (content) faults: the served payload is mutated so it
    # stays a VALID wire frame — header, CRC-equivalent structure, and
    # trailer untouched — and only the vector content lies.  Exercises
    # the trust plane end-to-end (dpwa_tpu/trust/, docs/trust.md).
    # ``byzantine_peers`` restricts which peers attack (() = all are
    # eligible); draws stay per (seed, round, peer) threefry streams.
    byzantine_peers: tuple[int, ...] = ()
    byzantine_start_round: int = 0
    byzantine_sign_probability: float = 0.0
    byzantine_scale_probability: float = 0.0
    byzantine_scale_factor: float = 100.0
    byzantine_replay_probability: float = 0.0
    byzantine_replay_age: int = 8
    byzantine_zero_probability: float = 0.0
    # Latency/bandwidth shaping (straggler injection, docs/flowctl.md).
    # ``trickle_windows`` serves a peer's frames at trickle_bytes_per_s
    # during [start, stop) — bytes FLOW but far below any useful rate, the
    # honest-but-overloaded shape the flowctl plane must soft-degrade
    # rather than quarantine.  ``stall_probability`` draws a jittered
    # mid-payload stall up to stall_ms_max; ``accept_delay_windows``
    # sleeps before the request read (accept-path lag).  All draws are
    # per (seed, round, peer) threefry streams like every other fault.
    trickle_windows: tuple[tuple[int, int, int], ...] = ()
    trickle_bytes_per_s: float = 2048.0
    stall_probability: float = 0.0
    stall_ms_max: float = 200.0
    accept_delay_windows: tuple[tuple[int, int, int], ...] = ()
    accept_delay_ms: float = 100.0
    # Link-quality flapping (self-tuning-wire chaos, docs/tune.md).
    # ``bandwidth_windows`` marks [start, stop) round intervals where a
    # peer's serving rate FLAPS: time is sliced into blocks of
    # ``bandwidth_block_rounds`` rounds, each block independently draws
    # whether it is shaped (chaos kind 13, vs bandwidth_flap_probability)
    # and — when shaped — a rate lerped across
    # [bandwidth_bps_min, bandwidth_bps_max] (kind 14).  Shaping composes
    # with trickle windows by taking the slower of the two, so a flapping
    # link looks like a square-wave trickle the tune controller must ride
    # without thrashing its ladder.
    bandwidth_windows: tuple[tuple[int, int, int], ...] = ()
    bandwidth_flap_probability: float = 1.0
    bandwidth_block_rounds: int = 4
    bandwidth_bps_min: float = 4096.0
    bandwidth_bps_max: float = 65536.0

    def __post_init__(self) -> None:
        for name in (
            "drop_probability",
            "delay_probability",
            "throttle_probability",
            "truncate_probability",
            "corrupt_probability",
            "partition_probability",
            "byzantine_sign_probability",
            "byzantine_scale_probability",
            "byzantine_replay_probability",
            "byzantine_zero_probability",
            "stall_probability",
            "bandwidth_flap_probability",
        ):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {v}")
        if self.byzantine_scale_factor <= 0:
            raise ValueError(
                f"byzantine_scale_factor must be > 0, "
                f"got {self.byzantine_scale_factor}"
            )
        if self.byzantine_replay_age < 1:
            raise ValueError(
                f"byzantine_replay_age must be >= 1, "
                f"got {self.byzantine_replay_age}"
            )
        if self.byzantine_start_round < 0:
            raise ValueError(
                f"byzantine_start_round must be >= 0, "
                f"got {self.byzantine_start_round}"
            )
        byz = tuple(int(p) for p in self.byzantine_peers)
        if any(p < 0 for p in byz):
            raise ValueError(f"bad byzantine_peers entry in {byz!r}")
        object.__setattr__(self, "byzantine_peers", byz)
        if self.delay_ms < 0:
            raise ValueError(f"delay_ms must be >= 0, got {self.delay_ms}")
        if self.throttle_bytes_per_s <= 0:
            raise ValueError(
                f"throttle_bytes_per_s must be > 0, "
                f"got {self.throttle_bytes_per_s}"
            )
        if self.partition_len_rounds < 1:
            raise ValueError(
                f"partition_len_rounds must be >= 1, "
                f"got {self.partition_len_rounds}"
            )
        if self.trickle_bytes_per_s <= 0:
            raise ValueError(
                f"trickle_bytes_per_s must be > 0, "
                f"got {self.trickle_bytes_per_s}"
            )
        if self.stall_ms_max < 0:
            raise ValueError(
                f"stall_ms_max must be >= 0, got {self.stall_ms_max}"
            )
        if self.accept_delay_ms < 0:
            raise ValueError(
                f"accept_delay_ms must be >= 0, got {self.accept_delay_ms}"
            )
        if self.bandwidth_block_rounds < 1:
            raise ValueError(
                f"bandwidth_block_rounds must be >= 1, "
                f"got {self.bandwidth_block_rounds}"
            )
        if self.bandwidth_bps_min <= 0:
            raise ValueError(
                f"bandwidth_bps_min must be > 0, "
                f"got {self.bandwidth_bps_min}"
            )
        if self.bandwidth_bps_max < self.bandwidth_bps_min:
            raise ValueError(
                f"bandwidth_bps_max must be >= bandwidth_bps_min, "
                f"got {self.bandwidth_bps_max} < {self.bandwidth_bps_min}"
            )
        for field in ("down_windows", "trickle_windows",
                      "accept_delay_windows", "bandwidth_windows"):
            windows = []
            for w in getattr(self, field):
                if isinstance(w, Mapping):
                    w = (w["peer"], w["start"], w["stop"])
                w = tuple(int(x) for x in w)
                if len(w) != 3 or w[0] < 0 or w[1] < 0 or w[2] < w[1]:
                    raise ValueError(f"bad {field} entry {w!r}")
                windows.append(w)
            object.__setattr__(self, field, tuple(windows))
        parts = []
        for w in self.partition_windows:
            if isinstance(w, Mapping):
                w = (w["group"], w["start"], w["stop"])
            group = tuple(sorted(int(p) for p in w[0]))
            start, stop = int(w[1]), int(w[2])
            if not group or min(group) < 0 or start < 0 or stop < start:
                raise ValueError(f"bad partition_windows entry {w!r}")
            parts.append((group, start, stop))
        object.__setattr__(self, "partition_windows", tuple(parts))
        links = []
        for w in self.link_windows:
            if isinstance(w, Mapping):
                w = (w["src"], w["dst"], w["start"], w["stop"])
            w = tuple(int(x) for x in w)
            if len(w) != 4 or min(w) < 0 or w[3] < w[2]:
                raise ValueError(f"bad link_windows entry {w!r}")
            links.append(w)
        object.__setattr__(self, "link_windows", tuple(links))


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """``recovery:`` block — crash recovery & divergence-guard knobs.

    Three concerns share these bounds deliberately (one definition of
    "sane replica" for the whole system):

    * the **remote guard** rejects a fetched payload whose vector is
      non-finite, whose L2 norm exceeds ``max_param_norm``, or whose
      advertised loss exceeds ``max_loss`` (classified as the
      ``poisoned`` detector outcome, never merged);
    * the **local rollback ring** restores the newest last-good snapshot
      when the local replica itself trips the same bounds;
    * the **interpolation rescue** (`interpolation._clamped`) treats a
      finite-but-huge local loss beyond the RESCUE bound as sick
      metadata, granting the full alpha=1 rescue.

    The rescue bound is deliberately NOT ``max_loss`` itself: the guard
    bound gets tuned down to the real loss scale of a workload (so a
    diverged peer's advertised loss is caught early), and a normal
    early-training loss spike can brush right up against it.  Crossing
    the guard bound costs one rejected frame or one ring rollback —
    recoverable either way — but the alpha=1 rescue REPLACES the local
    replica wholesale, which must be reserved for actually-diverged
    state.  ``rescue_loss`` (default ``16 * max_loss``) is that second,
    strictly-larger threshold; see :meth:`rescue_bound`.

    ``enabled`` also turns on STATE serving in the Rx server so a
    restarted peer can bootstrap over the blob wire (this forces the
    Python Rx server, like ``chaos.enabled`` — the native C++ loop only
    speaks the blob protocol)."""

    enabled: bool = True
    max_param_norm: float = 1e12
    max_loss: float = 1e9
    # Interpolation-rescue threshold: a finite LOCAL loss beyond this
    # bound counts as sick metadata deserving the alpha=1 rescue.  None
    # derives 16 * max_loss (see the class docstring for why the rescue
    # must sit well above the guard bound).
    rescue_loss: "float | None" = None
    # Zero-energy floor: reject a remote whose L2 norm falls below this
    # fraction of the LOCAL norm (a half-bootstrapped or byzantine peer
    # serving zeros would otherwise drag honest weights toward zero at
    # alpha-speed).  0 disables; only enforced when the caller knows its
    # own norm, so bare fetches without local context are unaffected.
    min_param_norm_ratio: float = 1e-4
    snapshot_every: int = 1
    snapshot_ring: int = 4
    state_chunk_bytes: int = 1 << 20
    bootstrap_timeout_ms: int = 10000
    max_resume_retries: int = 8
    max_clock_lag: float = 64.0
    auto_resync: bool = False

    def __post_init__(self) -> None:
        if self.max_param_norm <= 0:
            raise ValueError(
                f"max_param_norm must be > 0, got {self.max_param_norm}"
            )
        if self.max_loss <= 0:
            raise ValueError(f"max_loss must be > 0, got {self.max_loss}")
        if self.rescue_loss is not None and self.rescue_loss < self.max_loss:
            raise ValueError(
                f"rescue_loss must be >= max_loss ({self.max_loss}) — a "
                f"rescue below the guard bound would adopt a peer replica "
                f"wholesale on losses the guard still tolerates; got "
                f"{self.rescue_loss}"
            )
        if self.snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {self.snapshot_every}"
            )
        if self.snapshot_ring < 1:
            raise ValueError(
                f"snapshot_ring must be >= 1, got {self.snapshot_ring}"
            )
        if self.state_chunk_bytes < 64:
            raise ValueError(
                f"state_chunk_bytes must be >= 64, got {self.state_chunk_bytes}"
            )
        if self.bootstrap_timeout_ms < 1:
            raise ValueError(
                f"bootstrap_timeout_ms must be >= 1, "
                f"got {self.bootstrap_timeout_ms}"
            )
        if self.max_resume_retries < 0:
            raise ValueError(
                f"max_resume_retries must be >= 0, got {self.max_resume_retries}"
            )
        if self.max_clock_lag <= 0:
            raise ValueError(
                f"max_clock_lag must be > 0, got {self.max_clock_lag}"
            )
        if not 0.0 <= self.min_param_norm_ratio < 1.0:
            raise ValueError(
                f"min_param_norm_ratio must be in [0, 1), "
                f"got {self.min_param_norm_ratio}"
            )

    def rescue_bound(self) -> float:
        """The |loss| threshold for the interpolation alpha=1 rescue.

        ``rescue_loss`` when configured, else ``16 * max_loss`` — always
        at or above the guard's reject bound, so a loss the guard would
        merely reject/roll back never triggers wholesale adoption of a
        peer replica."""
        if self.rescue_loss is not None:
            return float(self.rescue_loss)
        return 16.0 * float(self.max_loss)


@dataclasses.dataclass(frozen=True)
class ViewConfig:
    """``membership.view:`` block — bounded partial views (docs/membership.md).

    Shrinks every control plane's horizon from the full ``nodes:``
    universe to a HyParView-style partial view: the **active** view is
    the peers this node gossips with and probes; the **passive** view is
    a churn-refreshed reservoir that supplies replacements when an
    active peer is evicted.  Digests are truncated to a threefry-drawn
    sample of ``digest_sample`` tracked peers per frame (wire format
    unchanged — receivers already merge arbitrary subsets), and the
    per-peer maps in trust / flowctl / scoreboard / membership are
    LRU-capped at ``state_cap``.

    Identity guarantee: with ``digest_sample >= N``, ``state_cap >= N``
    and ``active_size >= N - 1``, every frame and every plane decision
    is byte-identical to the global-view (``enabled: false``) behavior —
    sampling only ever truncates, never reorders or rewrites."""

    enabled: bool = False
    # Active view size: partner / relay / hedge draws range over (the
    # healthy subset of) these peers instead of all of ``nodes:``.
    active_size: int = 8
    # Passive reservoir size (candidates for promotion on failure).
    passive_size: int = 32
    # Tracked peers sampled into each published digest frame.
    digest_sample: int = 16
    # LRU cap on per-peer map residency across the scoreboard, trust,
    # deadline-estimator, and membership planes.  Evictions flow through
    # the PR 11 evict-listener path (tombstone + prune); QUARANTINED
    # peers with an unexpired streak and collapsed-trust peers are never
    # cap-evicted.
    state_cap: int = 64
    # Shuffle cadence: every this-many rounds one passive slot is
    # refreshed from the recently-seen universe (0 disables shuffling).
    shuffle_every: int = 8

    def __post_init__(self) -> None:
        if self.active_size < 1:
            raise ValueError(
                f"view.active_size must be >= 1, got {self.active_size}"
            )
        if self.passive_size < 0:
            raise ValueError(
                f"view.passive_size must be >= 0, got {self.passive_size}"
            )
        if self.digest_sample < 1:
            raise ValueError(
                f"view.digest_sample must be >= 1, got {self.digest_sample}"
            )
        if self.state_cap < 1:
            raise ValueError(
                f"view.state_cap must be >= 1, got {self.state_cap}"
            )
        if self.state_cap < self.active_size:
            raise ValueError(
                f"view.state_cap ({self.state_cap}) must be >= "
                f"view.active_size ({self.active_size}): the active view "
                f"is always tracked"
            )
        if self.shuffle_every < 0:
            raise ValueError(
                f"view.shuffle_every must be >= 0, got {self.shuffle_every}"
            )


@dataclasses.dataclass(frozen=True)
class MembershipConfig:
    """``membership:`` block — epidemic membership & partition tolerance.

    SWIM-style dissemination over the existing gossip wire: every frame
    carries an optional trailing digest (per-peer state, suspicion,
    incarnation); receivers merge it into their scoreboard so the whole
    ring converges on a shared membership view instead of each node
    rediscovering failures alone.  Needs ``health.enabled`` (the digest
    IS the scoreboard view) and forces the Python Rx server (the relay
    verb and digest trailer live there).  All decisions are keyed on
    gossip rounds and threefry draws — no wall clock — so membership
    event sequences are bit-identical across replays of a seed."""

    enabled: bool = True
    # Indirect probing: before promoting suspect -> quarantined on own
    # evidence, ask K deterministically-drawn healthy peers to
    # header-probe the suspect (0 = promote on own evidence alone).
    indirect_probes: int = 2
    relay_timeout_ms: int = 250
    # A quarantined peer that fails this many consecutive re-admission
    # probes is disseminated as ``dead`` (still probed locally — dead is
    # a gossip label, not a tombstone).
    dead_after_quarantines: int = 3
    # Churn hardening (docs/fleet.md): a peer the combined view holds
    # DEAD for this many further rounds is *evicted* — its scoreboard /
    # trust / flowctl per-peer state is pruned, it leaves the membership
    # digest (bounding digest growth under heavy join/leave), and the
    # partner remap never draws it.  A rejoiner refutes the dead claim
    # with a fresher incarnation and is re-admitted from scratch.
    # 0 disables eviction (legacy unbounded behavior).
    dead_gossip_rounds: int = 16
    # Degraded mode when |connected component| / n_peers falls BELOW
    # this fraction (strictly below: a 2-node ring losing one peer sits
    # exactly at 0.5 and is a peer failure, not a partition).
    quorum_fraction: float = 0.5
    # While degraded, scale interpolation alpha by this factor so a
    # minority island drifts more slowly from the majority (1.0 = off).
    degraded_alpha_scale: float = 1.0
    # Heal reconciliation: on seeing a component return, anti-entropy
    # merge with a drawn donor from the returning side, weighted by its
    # relative size, guarded by validate_payload + RollbackRing.
    heal_reconcile: bool = True
    # Reconcile only when the returning component is at least this
    # fraction of the ring — a single readmitted peer re-syncs itself
    # (recovery.max_clock_lag advice) rather than dragging everyone
    # through a state merge.
    reconcile_min_fraction: float = 0.3
    # Clamp on the returning side's merge weight, so even a majority
    # returning component cannot fully overwrite the local replica.
    max_heal_weight: float = 0.75
    # Bounded partial views (nested ``view:`` block; accepts a plain
    # dict from YAML).  Off by default: the global-view behavior of
    # every pre-view release.
    view: ViewConfig = dataclasses.field(default_factory=ViewConfig)

    def __post_init__(self) -> None:
        if isinstance(self.view, Mapping):
            object.__setattr__(self, "view", ViewConfig(**self.view))
        if self.indirect_probes < 0:
            raise ValueError(
                f"indirect_probes must be >= 0, got {self.indirect_probes}"
            )
        if self.relay_timeout_ms < 1:
            raise ValueError(
                f"relay_timeout_ms must be >= 1, got {self.relay_timeout_ms}"
            )
        if self.dead_after_quarantines < 1:
            raise ValueError(
                f"dead_after_quarantines must be >= 1, "
                f"got {self.dead_after_quarantines}"
            )
        if self.dead_gossip_rounds < 0:
            raise ValueError(
                f"dead_gossip_rounds must be >= 0, "
                f"got {self.dead_gossip_rounds}"
            )
        if not 0.0 <= self.quorum_fraction <= 1.0:
            raise ValueError(
                f"quorum_fraction must be in [0, 1], got {self.quorum_fraction}"
            )
        if not 0.0 < self.degraded_alpha_scale <= 1.0:
            raise ValueError(
                f"degraded_alpha_scale must be in (0, 1], "
                f"got {self.degraded_alpha_scale}"
            )
        if not 0.0 <= self.reconcile_min_fraction <= 1.0:
            raise ValueError(
                f"reconcile_min_fraction must be in [0, 1], "
                f"got {self.reconcile_min_fraction}"
            )
        if not 0.0 < self.max_heal_weight <= 1.0:
            raise ValueError(
                f"max_heal_weight must be in (0, 1], "
                f"got {self.max_heal_weight}"
            )


@dataclasses.dataclass(frozen=True)
class TrustConfig:
    """``trust:`` block — the content-trust plane's knobs (docs/trust.md).

    Screening defaults ON but conservative: classification only arms
    after ``min_window`` accepted exchanges (a cold ring has no baseline
    to deviate from), the MAD multipliers are wide (8σ suspect / 24σ
    reject — honest heterogeneity across data shards sits well inside),
    and a fully-trusted peer's alpha scale snaps to exactly 1.0, so an
    honest ring's trajectory is bit-identical to a trust-disabled run.
    Applies to the TCP transport (the path with per-peer payloads to
    screen); needs ``health.enabled`` for the quarantine feedback."""

    enabled: bool = True
    # Median/MAD window over ACCEPTED exchanges.  Larger = slower to
    # adapt to genuine regime changes, harder to poison; must comfortably
    # exceed min_window.
    window: int = 32
    min_window: int = 8
    # Robust z-score thresholds: [mad_multiplier, reject_multiplier) is
    # the damped band, beyond reject_multiplier the payload never merges.
    mad_multiplier: float = 8.0
    reject_multiplier: float = 24.0
    # Suspect merges at alpha * trust**damping; higher = harsher damping
    # for partially-trusted peers (1.0 = linear in trust).
    damping: float = 1.0
    # Trust EWMA: clean exchanges halve the trust DEFICIT every
    # ewma_half_life exchanges; verdict decays multiply trust down.
    ewma_half_life: float = 4.0
    suspect_decay: float = 0.7
    reject_decay: float = 0.25
    # Below this trust, every screening feeds an ``untrusted`` probe to
    # the scoreboard — a persistently-suspect peer quarantines even if no
    # single payload is outright rejected.
    quarantine_trust: float = 0.15
    # Hard bounds, active once armed, that no drifted baseline excuses:
    # a sign-flip lands at cosine -1; a scale blow-up below the recovery
    # guard's explosion bound still trips the norm ratio.
    cosine_floor: float = -0.5
    norm_ratio_max: float = 64.0
    # Replay detection: a peer's publish clock may run backward by this
    # much (re-serving last round's payload is normal overlap) before the
    # payload counts as a stale replay.
    replay_slack: float = 0.5
    # Re-acquaintance amnesty: a peer unscreened for more than
    # ``amnesty_gap * (n_peers - 1)`` rounds (partition, quarantine,
    # crash-rejoin — its replica has legitimately diverged) gets
    # ``amnesty_rounds`` lenient screenings in which hard rejections
    # downgrade to damped suspects and a stale clock resets the replay
    # base instead of rejecting.  0 on either knob disables amnesty.
    amnesty_gap: int = 4
    amnesty_rounds: int = 8

    def __post_init__(self) -> None:
        if self.window < 2:
            raise ValueError(f"window must be >= 2, got {self.window}")
        if not 1 <= self.min_window <= self.window:
            raise ValueError(
                f"min_window must be in [1, window], got {self.min_window}"
            )
        if self.mad_multiplier <= 0:
            raise ValueError(
                f"mad_multiplier must be > 0, got {self.mad_multiplier}"
            )
        if self.reject_multiplier < self.mad_multiplier:
            raise ValueError(
                "reject_multiplier must be >= mad_multiplier, "
                f"got {self.reject_multiplier} < {self.mad_multiplier}"
            )
        if self.damping <= 0:
            raise ValueError(f"damping must be > 0, got {self.damping}")
        if self.ewma_half_life <= 0:
            raise ValueError(
                f"ewma_half_life must be > 0, got {self.ewma_half_life}"
            )
        for name in ("suspect_decay", "reject_decay"):
            v = getattr(self, name)
            if not 0.0 <= v < 1.0:
                raise ValueError(f"{name} must be in [0, 1), got {v}")
        if not 0.0 < self.quarantine_trust < 1.0:
            raise ValueError(
                f"quarantine_trust must be in (0, 1), "
                f"got {self.quarantine_trust}"
            )
        if not -1.0 <= self.cosine_floor <= 1.0:
            raise ValueError(
                f"cosine_floor must be in [-1, 1], got {self.cosine_floor}"
            )
        if self.norm_ratio_max <= 1.0:
            raise ValueError(
                f"norm_ratio_max must be > 1, got {self.norm_ratio_max}"
            )
        if self.replay_slack < 0:
            raise ValueError(
                f"replay_slack must be >= 0, got {self.replay_slack}"
            )
        for name in ("amnesty_gap", "amnesty_rounds"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 0:
                raise ValueError(
                    f"{name} must be a non-negative int, got {v!r}"
                )


@dataclasses.dataclass(frozen=True)
class FlowctlConfig:
    """``flowctl:`` block — flow control plane knobs (docs/flowctl.md).

    Fetcher side: every classified fetch outcome feeds a per-peer
    latency/throughput estimator whose quantile sets the next fetch's
    cumulative deadline (clamped to ``[min_ms, max_ms]``; cold peers fall
    back to ``protocol.timeout_ms``), and once the un-margined quantile
    budget lapses a single hedged retry races the schedule's fallback
    partner.  Serving side: admission control in the Python Rx server —
    connection cap, per-remote token bucket, in-flight-bytes ceiling,
    slow-loris eviction — sheds excess load with an explicit ``DPWB``
    busy frame instead of queueing unboundedly.  Busy/slow evidence is
    low-weight (detector outcomes ``busy``/``slow``) and soft-degrades a
    peer (scoreboard ``degraded``, never quarantined on that evidence
    alone).  Like chaos/recovery/membership, enabling this forces the
    Python Rx server — the native C++ loop does not speak DPWB."""

    enabled: bool = True
    # Adaptive deadline: the tracked success-latency quantile, times
    # ``margin``, clamped to [min_ms, max_ms].  The un-margined quantile
    # is the hedge launch point, so the margin IS the hedge's headroom.
    quantile: float = 0.95
    margin: float = 1.5
    min_ms: float = 50.0
    max_ms: float = 5000.0
    # Per-peer success-latency samples kept (ring window); below
    # ``warmup`` samples the estimator is cold: deadlines fall back to
    # protocol.timeout_ms and hedging stays off.
    window: int = 32
    warmup: int = 5
    hedge: bool = True
    # Fraction of scheduled rounds deterministically remapped away from a
    # DEGRADED partner (threefry control draw, tag 8).  The rest still
    # fetch it — under its adaptive (short) budget — so recovery evidence
    # keeps flowing.  0 disables shedding, 1 starves the peer of direct
    # observations (readmission then rides on other peers' digests).
    degrade_shed_fraction: float = 0.5
    # Serving-side admission.
    max_connections: int = 32
    # Connection cap in effect under ``protocol.rx_server: reactor``:
    # the threaded cap bounds worker THREADS, the reactor's bounds
    # registered sockets (a few KB each), so it defaults 32× higher.
    # Every other admission knob is shared between the two servers.
    reactor_max_connections: int = 1024
    token_rate: float = 100.0
    token_burst: float = 200.0
    max_inflight_bytes: int = 1 << 28
    min_ingest_bytes_per_s: float = 4096.0
    # Per-connection handler budget; replaces the hard-coded 5 s
    # conn.settimeout in the accept path, so the request-read eviction
    # deadline and the handler recv timeout agree by construction.
    request_timeout_ms: int = 5000
    busy_retry_ms: int = 50

    def __post_init__(self) -> None:
        if not 0.0 < self.quantile <= 1.0:
            raise ValueError(
                f"quantile must be in (0, 1], got {self.quantile}"
            )
        if self.margin < 1.0:
            raise ValueError(f"margin must be >= 1, got {self.margin}")
        if not 0.0 < self.min_ms <= self.max_ms:
            raise ValueError(
                f"need 0 < min_ms <= max_ms, "
                f"got min_ms={self.min_ms} max_ms={self.max_ms}"
            )
        if self.window < 2:
            raise ValueError(f"window must be >= 2, got {self.window}")
        if not 1 <= self.warmup <= self.window:
            raise ValueError(
                f"warmup must be in [1, window], got {self.warmup}"
            )
        if not 0.0 <= self.degrade_shed_fraction <= 1.0:
            raise ValueError(
                f"degrade_shed_fraction must be in [0, 1], "
                f"got {self.degrade_shed_fraction}"
            )
        if self.max_connections < 1:
            raise ValueError(
                f"max_connections must be >= 1, got {self.max_connections}"
            )
        if self.reactor_max_connections < 1:
            raise ValueError(
                f"reactor_max_connections must be >= 1, "
                f"got {self.reactor_max_connections}"
            )
        if self.token_rate <= 0:
            raise ValueError(
                f"token_rate must be > 0, got {self.token_rate}"
            )
        if self.token_burst < 1:
            raise ValueError(
                f"token_burst must be >= 1, got {self.token_burst}"
            )
        if self.max_inflight_bytes < 1:
            raise ValueError(
                f"max_inflight_bytes must be >= 1, "
                f"got {self.max_inflight_bytes}"
            )
        if self.min_ingest_bytes_per_s <= 0:
            raise ValueError(
                f"min_ingest_bytes_per_s must be > 0, "
                f"got {self.min_ingest_bytes_per_s}"
            )
        if self.request_timeout_ms < 1:
            raise ValueError(
                f"request_timeout_ms must be >= 1, "
                f"got {self.request_timeout_ms}"
            )
        if self.busy_retry_ms < 0:
            raise ValueError(
                f"busy_retry_ms must be >= 0, got {self.busy_retry_ms}"
            )


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """``obs:`` block — observability plane (docs/observability.md).

    Three independently-gated facilities, all default-off because the
    contract is zero-cost-when-disabled: with this block off no trailing
    section is added to gossip frames, no tracing ``perf_counter`` calls
    run, and exchange byte streams are bit-identical to an obs-free
    build.

    - ``trace`` — per-stage round spans written as ``trace`` JSONL
      records, with the round's trace ID piggybacked on served frames
      (``DPWT`` trailing section) so ``tools/trace_report.py`` can join
      fetcher and server spans into one cross-peer timeline.  Forces the
      Python Rx server (like flowctl) so the serve leg can be timed.
    - ``sketch`` — a ``sketch_k``-float threefry-seeded random-projection
      sketch of the local replica piggybacked per frame, giving every
      peer an online ring-disagreement estimate.
    - ``metrics`` — a Prometheus text ``/metrics`` route on the healthz
      port, exposing counters/gauges from every enabled plane.
    - ``incidents`` — online anomaly detectors over the existing
      signals (fetch outcomes, scoreboard transitions, membership and
      trust events, the sketch's rel_rms, round wall time) feeding a
      correlator that folds alerts into open→evolve→resolve
      ``incident`` records (docs/incidents.md), served live at the
      ``/incidents`` healthz route.
    - ``recorder`` — a black-box flight recorder: a bounded in-memory
      ring of the last ``recorder_rounds`` rounds of
      outcomes/verdicts/digests, dumped to a post-mortem JSONL artifact
      on crash (atexit/SIGTERM), on incident open, on close, or via the
      ``/flightdump`` healthz route.

    ``log_max_bytes`` caps any JSONL file the adapter's MetricsLogger
    writes (health/exchange records), rolling through ``log_keep``
    generations (``<path>.1`` .. ``<path>.N``)."""

    trace: bool = False
    trace_every: int = 1
    trace_path: "str | None" = None
    trace_max_records: int = 4096
    sketch: bool = False
    sketch_k: int = 64
    sketch_every: int = 1
    metrics: bool = False
    log_max_bytes: int = 0
    log_keep: int = 1
    incidents: bool = False
    incident_path: "str | None" = None
    incident_window: int = 8
    incident_fail_streak: int = 2
    incident_soft_streak: int = 2
    incident_trust_burst: int = 2
    incident_storm_threshold: int = 3
    # staleness_storm detector (docs/async.md): stale drops within
    # ``incident_window`` rounds before the incident fires — lag
    # evidence is soft, so the bar sits above a lone straggler blip.
    incident_stale_storm: int = 3
    incident_stall_window: int = 8
    incident_stall_min_rel: float = 0.05
    incident_stall_improve: float = 0.01
    incident_slo_factor: float = 4.0
    incident_slo_rounds: int = 5
    incident_slo_warmup: int = 16
    incident_resolve_after: int = 8
    recorder: bool = False
    recorder_rounds: int = 64
    recorder_path: "str | None" = None

    def __post_init__(self) -> None:
        if self.trace_every < 1:
            raise ValueError(
                f"trace_every must be >= 1, got {self.trace_every}"
            )
        if self.trace_max_records < 1:
            raise ValueError(
                f"trace_max_records must be >= 1, "
                f"got {self.trace_max_records}"
            )
        if not 1 <= self.sketch_k <= 4096:
            raise ValueError(
                f"sketch_k must be in [1, 4096], got {self.sketch_k}"
            )
        if self.sketch_every < 1:
            raise ValueError(
                f"sketch_every must be >= 1, got {self.sketch_every}"
            )
        if self.log_max_bytes < 0:
            raise ValueError(
                f"log_max_bytes must be >= 0, got {self.log_max_bytes}"
            )
        if self.log_keep < 1:
            raise ValueError(
                f"log_keep must be >= 1, got {self.log_keep}"
            )
        for name in (
            "incident_window",
            "incident_fail_streak",
            "incident_soft_streak",
            "incident_trust_burst",
            "incident_storm_threshold",
            "incident_stale_storm",
            "incident_stall_window",
            "incident_slo_rounds",
            "incident_slo_warmup",
            "incident_resolve_after",
            "recorder_rounds",
        ):
            v = getattr(self, name)
            if v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        if self.incident_stall_min_rel < 0:
            raise ValueError(
                f"incident_stall_min_rel must be >= 0, "
                f"got {self.incident_stall_min_rel}"
            )
        if not 0.0 <= self.incident_stall_improve < 1.0:
            raise ValueError(
                f"incident_stall_improve must be in [0, 1), "
                f"got {self.incident_stall_improve}"
            )
        if self.incident_slo_factor <= 1.0:
            raise ValueError(
                f"incident_slo_factor must be > 1, "
                f"got {self.incident_slo_factor}"
            )

    @property
    def enabled(self) -> bool:
        """Any facility on (the transport builds obs state iff this)."""
        return (
            self.trace or self.sketch or self.metrics
            or self.incidents or self.recorder
        )


@dataclasses.dataclass(frozen=True)
class InterpolationConfig:
    type: str = "constant"
    factor: float = 0.5

    def __post_init__(self) -> None:
        if self.type not in ("constant", "clock", "loss"):
            raise ValueError(f"unknown interpolation type {self.type!r}")
        if not 0.0 <= self.factor <= 1.0:
            raise ValueError(f"factor must be in [0, 1], got {self.factor}")


@dataclasses.dataclass(frozen=True)
class IslandSpec:
    """One ``topology.islands`` entry: a named subset of ``nodes:``."""

    name: str
    nodes: tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Two-level (island × wide-area) gossip topology; docs/hierarchy.md.

    An empty ``islands`` tuple (the default, and the absent-block case)
    means the flat single-ring topology — every pre-hierarchy config
    keeps its exact behavior."""

    islands: tuple[IslandSpec, ...] = ()
    leader_seed: int = 0
    intra_rounds: int = 1

    def __post_init__(self) -> None:
        if self.intra_rounds < 1:
            raise ValueError(
                f"topology.intra_rounds must be >= 1, got {self.intra_rounds}"
            )
        names = [isl.name for isl in self.islands]
        if len(set(names)) != len(names):
            dupes = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(f"duplicate island names in topology: {dupes}")
        for isl in self.islands:
            if not isl.nodes:
                raise ValueError(
                    f"topology island {isl.name!r} lists no nodes"
                )
            if len(set(isl.nodes)) != len(isl.nodes):
                dupes = sorted(
                    {n for n in isl.nodes if isl.nodes.count(n) > 1}
                )
                raise ValueError(
                    f"topology island {isl.name!r} lists node(s) {dupes}"
                    " more than once"
                )

    @property
    def enabled(self) -> bool:
        """Hierarchical mode on — at least one island is declared."""
        return bool(self.islands)

    def validate_nodes(self, node_names: Sequence[str]) -> None:
        """Cross-check the island partition against the ``nodes:`` list.

        Every error names the offending island and node: islands must
        reference only declared nodes, no node may belong to two
        islands, and — when the block is enabled — every node must be
        covered (a super-peer topology with stragglers outside any
        island has no one to speak for them)."""
        if not self.enabled:
            return
        known = set(node_names)
        owner: dict[str, str] = {}
        for isl in self.islands:
            for node in isl.nodes:
                if node not in known:
                    raise ValueError(
                        f"topology island {isl.name!r} references unknown"
                        f" node {node!r} (declared nodes:"
                        f" {sorted(known)})"
                    )
                if node in owner:
                    raise ValueError(
                        f"node {node!r} appears in both island"
                        f" {owner[node]!r} and island {isl.name!r} — a"
                        " node belongs to exactly one island"
                    )
                owner[node] = isl.name
        uncovered = [n for n in node_names if n not in owner]
        if uncovered:
            raise ValueError(
                f"topology islands do not cover node(s) {uncovered} — every"
                " node must belong to exactly one island"
            )


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """``run:`` block — the training-harness loop (docs/training.md).

    Knobs for :mod:`dpwa_tpu.run`: how many optimizer steps each node
    takes, the SGD hyperparameters, the loss-record cadence, and the
    periodic-checkpoint policy the crash leg restarts from.  The data
    order is NOT configured here: each node's per-epoch shuffle is a
    threefry draw keyed on ``(protocol.seed, epoch, node)``
    (``schedules.data_shuffle_draw``), so a seeded rerun replays the
    exact batch sequence with no stream state to save."""

    steps: int = 100
    batch_size: int = 32
    lr: float = 0.1
    momentum: float = 0.0
    loss_every: int = 1
    checkpoint_every: int = 0
    checkpoint_dir: "str | None" = None
    checkpoint_keep: int = 3
    target_loss: float = 0.0

    def __post_init__(self) -> None:
        if self.steps < 1:
            raise ValueError(f"run.steps must be >= 1, got {self.steps}")
        if self.batch_size < 1:
            raise ValueError(
                f"run.batch_size must be >= 1, got {self.batch_size}"
            )
        if self.lr <= 0:
            raise ValueError(f"run.lr must be > 0, got {self.lr}")
        if not 0.0 <= self.momentum < 1.0:
            raise ValueError(
                f"run.momentum must be in [0, 1), got {self.momentum}"
            )
        if self.loss_every < 1:
            raise ValueError(
                f"run.loss_every must be >= 1, got {self.loss_every}"
            )
        if self.checkpoint_every < 0:
            raise ValueError(
                f"run.checkpoint_every must be >= 0, "
                f"got {self.checkpoint_every}"
            )
        if self.checkpoint_keep < 1:
            raise ValueError(
                f"run.checkpoint_keep must be >= 1, "
                f"got {self.checkpoint_keep}"
            )
        if self.target_loss < 0:
            raise ValueError(
                f"run.target_loss must be >= 0, got {self.target_loss}"
            )


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """``tune:`` block — the self-tuning wire (docs/tune.md).

    Off (the default, and the absent-block case) the transport publishes
    exactly what the static ``protocol.wire_*`` knobs say — frames stay
    byte-identical to a pre-tune build.  On, a per-link
    :class:`~dpwa_tpu.tune.controller.LinkTuner` (the DeadlineEstimator
    mold) walks each link up and down the frozen codec ladder from the
    observations the obs planes already collect: escalate compression on
    wire-bound links, back off when the sketch plane shows convergence
    stalling, and shed fidelity — never rounds — while the scheduled
    partner is scoreboard-DEGRADED.  Every decision derives from
    QUANTIZED observations plus one registered threefry stream (tag 37,
    dwell jitter), so seeded soaks replay their decision logs
    bit-identically."""

    enabled: bool = False
    # Observation rounds per link behind each decision.
    window: int = 8
    # Hysteresis: a link holds a rung at least this many rounds before
    # it may escalate again, and may not re-escalate for
    # ``cooldown_rounds`` after a back-off — a square-wave (flapping)
    # link settles instead of thrashing the ladder.
    min_dwell_rounds: int = 6
    cooldown_rounds: int = 12
    # A round is "wire-bound" when its quantized wire-span fraction of
    # the round wall is at/above this.
    wire_bound_frac: float = 0.5
    # Escalate one rung when at least this fraction of the window's
    # rounds are wire-bound (busy/slow/stale outcomes count as
    # wire-bound evidence — the link is failing to move bytes in time).
    escalate_frac: float = 0.5
    # Back off one rung when the window's fractional rel_rms improvement
    # falls below this (the sketch plane says compression is starving
    # convergence).  Only meaningful with >= 2 rel samples in-window.
    stall_eps: float = 0.02
    # Extra rungs (clamped to the ladder top) shed while the scheduled
    # partner is DEGRADED — fidelity shed replaces the degrade_shed
    # round-drop remap while the controller is enabled.
    shed_rungs: int = 2
    # Quantization buckets for observed span fractions and rel trends;
    # decisions never branch on raw wall-clock readings.
    quant: int = 16
    # Dwell jitter (threefry tag 37) in [0, jitter_rounds] added to each
    # link's dwell expiry so fleet-wide escalations desynchronize.
    jitter_rounds: int = 2

    def __post_init__(self) -> None:
        if self.window < 2:
            raise ValueError(f"tune.window must be >= 2, got {self.window}")
        if self.min_dwell_rounds < 1:
            raise ValueError(
                f"tune.min_dwell_rounds must be >= 1, "
                f"got {self.min_dwell_rounds}"
            )
        if self.cooldown_rounds < 0:
            raise ValueError(
                f"tune.cooldown_rounds must be >= 0, "
                f"got {self.cooldown_rounds}"
            )
        for name in ("wire_bound_frac", "escalate_frac"):
            v = getattr(self, name)
            if not 0.0 < v <= 1.0:
                raise ValueError(f"tune.{name} must be in (0, 1], got {v}")
        if self.stall_eps < 0:
            raise ValueError(
                f"tune.stall_eps must be >= 0, got {self.stall_eps}"
            )
        if self.shed_rungs < 0:
            raise ValueError(
                f"tune.shed_rungs must be >= 0, got {self.shed_rungs}"
            )
        if self.quant < 2:
            raise ValueError(f"tune.quant must be >= 2, got {self.quant}")
        if self.jitter_rounds < 0:
            raise ValueError(
                f"tune.jitter_rounds must be >= 0, "
                f"got {self.jitter_rounds}"
            )


@dataclasses.dataclass(frozen=True)
class DpwaConfig:
    nodes: tuple[NodeSpec, ...]
    protocol: ProtocolConfig = ProtocolConfig()
    shard: ShardConfig = ShardConfig()
    interpolation: InterpolationConfig = InterpolationConfig()
    health: HealthConfig = HealthConfig()
    chaos: ChaosConfig = ChaosConfig()
    recovery: RecoveryConfig = RecoveryConfig()
    membership: MembershipConfig = MembershipConfig()
    trust: TrustConfig = TrustConfig()
    flowctl: FlowctlConfig = FlowctlConfig()
    obs: ObsConfig = ObsConfig()
    topology: TopologyConfig = TopologyConfig()
    run: RunConfig = RunConfig()
    tune: TuneConfig = TuneConfig()

    def __post_init__(self) -> None:
        # Errors here name the offending island/node (satellite fix):
        # the partition is validated against the ACTUAL nodes: list, not
        # just internally.
        self.topology.validate_nodes(self.node_names)

    @property
    def n_peers(self) -> int:
        """Length of ``nodes:`` — the size of the gossip mesh axis."""
        return len(self.nodes)

    @property
    def node_names(self) -> tuple[str, ...]:
        return tuple(n.name for n in self.nodes)

    def node_index(self, name: str) -> int:
        """Position of ``name`` in ``nodes:`` — this process/device's peer id."""
        try:
            return self.node_names.index(name)
        except ValueError:
            raise KeyError(
                f"node {name!r} not in config (have {self.node_names})"
            ) from None

    def node(self, name: str) -> NodeSpec:
        return self.nodes[self.node_index(name)]


def _build_nodes(raw: Sequence[Any]) -> tuple[NodeSpec, ...]:
    nodes = []
    for i, entry in enumerate(raw):
        if isinstance(entry, str):
            # Shorthand: a bare name (ICI transport needs no address).
            nodes.append(NodeSpec(name=entry))
        elif isinstance(entry, Mapping):
            nodes.append(
                NodeSpec(
                    name=str(entry.get("name", f"node{i}")),
                    host=str(entry.get("host", "127.0.0.1")),
                    port=int(entry.get("port", 0)),
                )
            )
        else:
            raise TypeError(f"bad nodes[{i}] entry: {entry!r}")
    names = [n.name for n in nodes]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate node names in config: {names}")
    if not nodes:
        raise ValueError("config must list at least one node")
    return tuple(nodes)


def _build_islands(raw: Sequence[Any]) -> tuple[IslandSpec, ...]:
    islands = []
    for i, entry in enumerate(raw):
        if isinstance(entry, Mapping):
            islands.append(
                IslandSpec(
                    name=str(entry.get("name", f"island{i}")),
                    nodes=tuple(str(n) for n in (entry.get("nodes") or ())),
                )
            )
        elif isinstance(entry, Sequence) and not isinstance(entry, (str, bytes)):
            # Shorthand: a bare member list gets a positional island name.
            islands.append(
                IslandSpec(
                    name=f"island{i}", nodes=tuple(str(n) for n in entry)
                )
            )
        else:
            raise TypeError(f"bad topology.islands[{i}] entry: {entry!r}")
    return tuple(islands)


def config_from_dict(raw: Mapping[str, Any]) -> DpwaConfig:
    """Build a :class:`DpwaConfig` from a parsed-YAML mapping."""
    if "nodes" not in raw:
        raise ValueError("config is missing the required 'nodes:' list")
    proto = dict(raw.get("protocol") or {})
    shard = dict(raw.get("shard") or {})
    interp = dict(raw.get("interpolation") or {})
    health = dict(raw.get("health") or {})
    chaos = dict(raw.get("chaos") or {})
    recovery = dict(raw.get("recovery") or {})
    membership = dict(raw.get("membership") or {})
    trust = dict(raw.get("trust") or {})
    flowctl = dict(raw.get("flowctl") or {})
    obs = dict(raw.get("obs") or {})
    topology = dict(raw.get("topology") or {})
    run = dict(raw.get("run") or {})
    tune = dict(raw.get("tune") or {})
    if topology.get("islands") is not None:
        topology["islands"] = _build_islands(topology["islands"])
    for key in (
        "down_windows", "partition_windows", "link_windows",
        "byzantine_peers", "trickle_windows", "accept_delay_windows",
        "bandwidth_windows",
    ):
        if chaos.get(key) is not None:
            chaos[key] = tuple(chaos[key])
    return DpwaConfig(
        nodes=_build_nodes(raw["nodes"]),
        protocol=ProtocolConfig(**proto),
        shard=ShardConfig(**shard),
        interpolation=InterpolationConfig(**interp),
        health=HealthConfig(**health),
        chaos=ChaosConfig(**chaos),
        recovery=RecoveryConfig(**recovery),
        membership=MembershipConfig(**membership),
        trust=TrustConfig(**trust),
        flowctl=FlowctlConfig(**flowctl),
        obs=ObsConfig(**obs),
        topology=TopologyConfig(**topology),
        run=RunConfig(**run),
        tune=TuneConfig(**tune),
    )


def load_config(path: str) -> DpwaConfig:
    """Load the reference-style YAML config file."""
    with open(path, "r", encoding="utf-8") as f:
        raw = yaml.safe_load(f)
    if not isinstance(raw, Mapping):
        raise ValueError(f"config file {path} did not parse to a mapping")
    return config_from_dict(raw)


def make_local_config(
    n_peers: int,
    *,
    schedule: str = "ring",
    fetch_probability: float = 1.0,
    interpolation: str = "constant",
    factor: float = 0.5,
    seed: int = 0,
    base_port: int = 45000,
    health: "HealthConfig | Mapping[str, Any] | None" = None,
    chaos: "ChaosConfig | Mapping[str, Any] | None" = None,
    recovery: "RecoveryConfig | Mapping[str, Any] | None" = None,
    membership: "MembershipConfig | Mapping[str, Any] | None" = None,
    trust: "TrustConfig | Mapping[str, Any] | None" = None,
    flowctl: "FlowctlConfig | Mapping[str, Any] | None" = None,
    obs: "ObsConfig | Mapping[str, Any] | None" = None,
    topology: "TopologyConfig | Mapping[str, Any] | None" = None,
    shard: "ShardConfig | Mapping[str, Any] | None" = None,
    run: "RunConfig | Mapping[str, Any] | None" = None,
    tune: "TuneConfig | Mapping[str, Any] | None" = None,
    **protocol_kwargs: Any,
) -> DpwaConfig:
    """Programmatic config for tests/benchmarks: n local peers on 127.0.0.1.

    Node ``i`` listens on ``base_port + i``; ``base_port=0`` gives EVERY node
    port 0, so the OS picks each server's port and the caller reads it back
    (``TcpTransport.port``) and wires the ring with ``set_peer_port``.

    ``health`` / ``chaos`` / ``recovery`` / ``membership`` / ``trust`` /
    ``flowctl`` / ``obs`` accept a config object or a plain dict (the
    YAML-block shorthand)."""
    if isinstance(health, Mapping):
        health = HealthConfig(**health)
    if isinstance(chaos, Mapping):
        chaos = ChaosConfig(**chaos)
    if isinstance(recovery, Mapping):
        recovery = RecoveryConfig(**recovery)
    if isinstance(membership, Mapping):
        membership = MembershipConfig(**membership)
    if isinstance(trust, Mapping):
        trust = TrustConfig(**trust)
    if isinstance(flowctl, Mapping):
        flowctl = FlowctlConfig(**flowctl)
    if isinstance(obs, Mapping):
        obs = ObsConfig(**obs)
    if isinstance(shard, Mapping):
        shard = ShardConfig(**shard)
    if isinstance(run, Mapping):
        run = RunConfig(**run)
    if isinstance(tune, Mapping):
        tune = TuneConfig(**tune)
    if isinstance(topology, Mapping):
        topology = dict(topology)
        if topology.get("islands") is not None:
            topology["islands"] = _build_islands(topology["islands"])
        topology = TopologyConfig(**topology)
    return DpwaConfig(
        nodes=tuple(
            NodeSpec(
                name=f"node{i}", host="127.0.0.1",
                port=base_port + i if base_port else 0,
            )
            for i in range(n_peers)
        ),
        protocol=ProtocolConfig(
            schedule=schedule,
            fetch_probability=fetch_probability,
            seed=seed,
            **protocol_kwargs,
        ),
        interpolation=InterpolationConfig(type=interpolation, factor=factor),
        health=health if health is not None else HealthConfig(),
        chaos=chaos if chaos is not None else ChaosConfig(),
        recovery=recovery if recovery is not None else RecoveryConfig(),
        membership=membership if membership is not None else MembershipConfig(),
        trust=trust if trust is not None else TrustConfig(),
        flowctl=flowctl if flowctl is not None else FlowctlConfig(),
        obs=obs if obs is not None else ObsConfig(),
        topology=topology if topology is not None else TopologyConfig(),
        shard=shard if shard is not None else ShardConfig(),
        run=run if run is not None else RunConfig(),
        tune=tune if tune is not None else TuneConfig(),
    )
