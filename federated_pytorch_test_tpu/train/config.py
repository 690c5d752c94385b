"""Run configuration.

The reference configures each driver with module-level constants edited
in-source (federated_multi.py:9-48, consensus_multi.py:9-59).  The rebuild
keeps the same knob *names* in one dataclass per entry point (SURVEY.md
section 5 "Config / flag system"); ``use_cuda`` becomes ``use_tpu``
(BASELINE.json).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class FederatedConfig:
    """Knobs shared by every CIFAR10 federated driver.

    Defaults follow federated_multi.py:9-48 / consensus_multi.py:9-59.
    """

    K: int = 10                    # number of models (== slaves/clients)
    default_batch: int = 128       # minibatch size
    Nloop: int = 12                # loops over the whole network
    Nepoch: int = 1                # local epochs per round
    Nadmm: int = 3                 # communication (averaging/ADMM) rounds
    seed: int = 69                 # torch.manual_seed(69) analogue
    init_seed: int = 0             # common-init seed (federated_multi.py:126)

    # regularisation (federated_multi.py:27-28, consensus_multi.py:27-29)
    lambda1: float = 1e-4          # L1
    lambda2: float = 1e-4          # L2
    admm_rho0: float = 1.0         # FedProx rho / ADMM penalty (0.1 for consensus)

    # flags (federated_multi.py:30-43)
    load_model: bool = False
    init_model: bool = True
    save_model: bool = True
    check_results: bool = True
    biased_input: bool = False
    be_verbose: bool = False
    use_resnet: bool = False
    use_tpu: bool = True           # reference `use_cuda` (BASELINE.json rename)
    # classifier architecture: the reference switches models by editing the
    # source (uncommenting Net()/Net1()/Net2()/ResNet18(), e.g.
    # federated_multi.py:92-97); here it is a flag.  "auto" preserves the
    # use_resnet semantics (resnet18 when set, else net).
    model: str = "auto"            # auto|net|net1|net2|resnet9|resnet18
    # ResNet normalisation: "batch" = reference parity (per-client running
    # stats); "group" = GroupNorm(32), stat-free and pod-scale safe
    # (models/resnet.py module docstring).  Ignored by the BN-free Net.
    norm: str = "batch"

    # partial client participation: each communication round samples every
    # client independently with this probability (at least one is always
    # kept); inactive clients neither train nor exchange that round —
    # params, optimizer state and ADMM duals stay untouched until next
    # sampled.  1.0 = reference parity (all K clients every round;
    # partial participation is the FedProx paper's motivating regime,
    # cited at reference README.md:17 but never implemented there).
    # Incompatible with bb_update (the BB spectral history assumes every
    # client moves every round).
    participation: float = 1.0

    # population federation (population/): register `population` virtual
    # clients (target 10k+) while the device mesh still compiles over K
    # slots — each communication round a seeded sampler draws a K-id
    # cohort (a pure function of seed + round coordinates, so kill/
    # resume and mesh reshape redraw the identical sequence, replayable
    # via control.replay), the round kernel gathers the cohort's
    # registry state (quarantine, membership, async ledger, EF rows)
    # into its [K] slot arrays, and the slots scatter back afterwards —
    # per-round cost is cohort-bounded, not population-bounded.  0 = off
    # (the literal pre-population engine, bitwise); population == K is
    # full participation and also bitwise the existing engine.
    # Requires population >= K; incompatible with bb_update (slot
    # occupancy changes per round, breaking the BB spectral history),
    # biased_input, fused_rounds, device_data and overlap_staging (the
    # cohort's data rows are re-indexed on the host staging path).
    population: int = 0
    # cohort sampling method (population/sampler.py SAMPLER_CHOICES):
    # uniform | weighted (static seeded availability weights) |
    # stratified (one id per contiguous id stratum — guaranteed spread)
    cohort_sampling: str = "uniform"
    # live cohort-size knob: the fraction of the K cohort slots active
    # per round (>= 1/K; seeded slot choice).  The control plane's
    # cohort rung shrinks this under throughput collapse and regrows it
    # on quiet (control/policy.py); the restart supervisor's degraded
    # ladder lowers it for population runs (control/supervisor.py).
    cohort_frac: float = 1.0

    # lossy update compression (compress/): each comm round the client
    # ships encode(x_k - z) instead of the dense f32 block vector and the
    # server averages the reconstructions.  "none" = reference parity
    # (bit-identical dense path).  q8/q4: stochastic uniform quantization
    # with per-chunk scales (quant_chunk values per scale); topk: keep the
    # topk_frac largest-|.| coordinates (pair with error_feedback, which
    # carries the dropped mass into the next round's update).
    compress: str = "none"         # none|q8|q4|topk
    topk_frac: float = 0.01
    quant_chunk: int = 256
    error_feedback: bool = False

    # fault injection (train/faults.py): deterministic, seeded, replayable
    # per-client per-round faults — dropout, straggler delay (local epochs
    # withheld, stale update shipped), update corruption (nan/inf/
    # signflip/scale elementwise; innerprod/collude coordinated) at the
    # encode(x_k - z) boundary, and late delivery (delay=, async mode
    # only).  "none" = no faults (reference parity).  Grammar:
    #   drop=P,straggle=P,corrupt=P,mode=M,scale=X,seed=N,clients=i+j,
    #   delay=P,delay_max=N,join=P,leave=P,preempt=P
    fault_spec: str = "none"

    # soak campaigns (campaign/): a trace-driven heavy-traffic schedule
    # compiled per round into the seeded fault/churn families — diurnal
    # arrival curves, churn waves, straggler storms, correlated
    # corruption bursts, deterministic preemption events — recorded as
    # additive `campaign` records (schema v12) that control.replay
    # re-derives bit-exactly.  "none" = campaign off (the literal seed
    # path, bitwise).  Mutually exclusive with fault_spec (the campaign
    # OWNS the fault families' probabilities per round).  Grammar:
    #   hours=H,round_minutes=M,diurnal=A,drop=P,straggle=P,corrupt=P,
    #   mode=M,scale=X,join=P,leave=P,storm=P,storm_len=N,
    #   storm_straggle=P,burst=P,burst_len=N,burst_corrupt=P,
    #   preempt_at=h1+h2,seed=N,accel=X,health_window_hours=H
    campaign_spec: str = "none"
    # virtual-clock acceleration override (virtual seconds per wall
    # second) for the soak harness; 0 = use the spec's accel= (else
    # real time).  Scheduling-inert: scales only actual sleeps, never
    # any recorded value (PARITY.md v0.13).
    campaign_accel: float = 0.0

    # serving plane (serve/): batched online inference over the
    # consensus state, ridden at every round boundary — the consensus
    # weights hot-swap into a double-buffered predictor (never torn:
    # each request batch is answered by exactly one weights version),
    # seeded synthetic traffic (draw tag 83, campaign-style diurnal
    # wave) flows through a pad-to-bucket micro-batcher, and the served
    # answers double as an eval stream feeding the serve_drift health
    # rule and (act mode) the control plane's refresh_serving rung.
    # Every planning field of the additive `serve` record (schema v13)
    # — requests, batch plan, weights_version = 1 + round // swap_every,
    # drift injection — is a pure function of (seed, round_index), so
    # control.replay re-derives it from the header config and no serve
    # state rides in checkpoints; latency/QPS/swap-gap/accuracy are
    # advisory.  "none" = serving off, the literal seed path (bitwise —
    # golden-digest gated).  Grammar:
    #   qps=N,round_minutes=M,diurnal=A,buckets=8+32+128,swap_every=N,
    #   drift_at=R,seed=N
    serve_spec: str = "none"

    # elastic federation (mesh-reshaping resume): allow a checkpoint
    # written on a D-device mesh to restore onto a D'-device mesh — the
    # [K, ...] client stack restages onto the surviving mesh (K % D' must
    # still divide), replicated server state re-lays out, and the jitted
    # fns rebuild over the new geometry.  Off by default: a wrong-D
    # resume then fails with a typed CheckpointGeometryError instead of
    # silently continuing on different hardware (PARITY.md: bitwise when
    # D' == D, allclose + exact history semantics when D' != D).
    elastic_resume: bool = False

    # preemption-tolerant collectives (parallel/mesh.py bounded_wait):
    # bound every multi-process barrier/collective entry point by this
    # many seconds — a peer process lost to preemption then surfaces as
    # a typed CollectiveTimeoutError (which the restart supervisor's
    # reshape rung can act on) instead of an infinite wedge.  0 = off
    # (the literal unwrapped call — default path bit-identical and
    # thread-free).  Also settable via env FEDTPU_BARRIER_TIMEOUT.
    barrier_timeout: float = 0.0

    # robust aggregation (parallel/comm.py robust_federated_mean):
    # drop-in alternatives to the plain psum mean — coordinate-wise
    # trimmed mean ("trim", trims trim_frac per side; tolerates an
    # attacker fraction < trim_frac), coordinate median ("median",
    # breakdown ~1/2), norm-clipped mean ("clip", clips every client to
    # clip_mult x the median active norm), multi-Krum selection ("krum",
    # averages the m - f closest-to-their-neighbours clients with
    # f = floor(trim_frac * m) — survives coordinated colluders), and
    # the Weiszfeld geometric median ("geomed", per-client breakdown
    # ~1/2).  "none" = the literal dense psum mean (reference parity).
    robust_agg: str = "none"       # one of comm.ROBUST_AGG_CHOICES
    trim_frac: float = 0.1
    clip_mult: float = 3.0
    # chunked robust aggregation (parallel/comm.py
    # robust_federated_mean_chunked): own the coordinate axis instead of
    # the client axis — one tiled all_to_all lands a [K, ceil(N/D)]
    # segment slab per device in place of the all-gathered [K, N]
    # matrix, the estimator runs on the owned coordinates, and a small
    # all_gather re-replicates the result.  1/D the peak working set
    # (gated by compiled memory_analysis in the tests); trim/median are
    # bitwise the dense estimator, clip/krum/geomed allclose (psum'd
    # norm/Gram reductions re-associate — PARITY.md).  Requires
    # --robust-agg != none.  Off by default.
    robust_chunked: bool = False

    # update guards + quarantine (train/engine.py): validate every
    # incoming client delta before aggregation — finite, and norm within
    # guard_norm_mult x the running mean accepted norm (per block; no
    # norm bound until one clean round has calibrated it).  Offenders are
    # masked out of the round (partial-participation plumbing) and
    # quarantined for quarantine_rounds subsequent rounds; an
    # error-feedback residual of a quarantined client is reset (see
    # compress/error_feedback.py reset_state).  A round where ALL
    # clients are rejected degrades gracefully: z carries over, the run
    # continues.  Off by default: guards add guard_trips/quarantined
    # history fields, and the default history must stay numerically
    # identical to the pre-guard dense path.
    update_guard: bool = False
    guard_norm_mult: float = 10.0
    quarantine_rounds: int = 1

    # buffered-asynchronous federation (train/engine.py
    # _round_activity_async): the server stops barriering per round —
    # each client's update is dispatched when it finishes local work and
    # spends a seeded number of rounds in transit (fault_spec delay=
    # family), the server folds updates in AS THEY ARRIVE with
    # staleness-decayed weights w = (1 + s)^(-staleness_alpha), and an
    # admission controller rejects anything staler than max_staleness
    # rounds.  A client with an update in flight does not start new
    # work (one outstanding update per client — the "buffer" is the
    # frozen client params themselves).  Deterministic given the seed,
    # and resume-stable: the staleness ledger rides in the mid-run
    # checkpoint.  Off by default — the synchronous barrier path stays
    # bit-identical.  Incompatible with bb_update (the BB spectral
    # history assumes lockstep rounds).
    async_rounds: bool = False
    max_staleness: int = 4         # admission cutoff, in comm rounds
    staleness_alpha: float = 0.5   # polynomial decay exponent (0 = flat)

    # adaptive-ADMM Barzilai-Borwein knobs (consensus_multi.py:41-47)
    bb_update: bool = False
    bb_period_T: int = 2
    bb_alphacorrmin: float = 0.2
    bb_epsilon: float = 1e-3
    bb_rhomax: float = 0.1

    # optimizer (the references hardcode Adam lr=1e-3, federated_multi.py:159;
    # the commented-out alternative is LBFGSNew(history_size=10, max_iter=4,
    # line_search_fn=True, batch_mode=True), federated_multi.py:158)
    optimizer: str = "adam"        # "adam" | "lbfgs"
    lr: float = 1e-3
    bf16: bool = False             # bfloat16 compute for convs/dense (MXU rate)
    lbfgs_history_size: int = 10
    lbfgs_max_iter: int = 4

    # data
    data_dir: Optional[str] = None
    drop_last_sample: bool = True  # reference off-by-one parity
    # device-resident training data: stage each client's raw uint8 shard
    # into HBM ONCE and build every epoch's shuffled batches with an
    # on-device permutation gather — the per-epoch host shuffle + H2D copy
    # (the dominant cost of a production round when the host link is slow)
    # disappears from the steady state.  None = auto: on when the training
    # set fits the HBM budget (FEDTPU_DEVICE_DATA_MB, default 2048).
    device_data: Optional[bool] = None
    # stage epoch n+1's batches while epoch n computes (device_data off:
    # overlaps the host shuffle + H2D copy with device work).  On by
    # default — --no-prefetch isolates the staging overhead when profiling.
    prefetch: bool = True

    # fused round execution: when epoch data is device-resident
    # (device_data), collapse the Nepoch-epoch host loop AND the
    # communication update into ONE jitted dispatch per round — epoch PRNG
    # keys are derived on-device from the same counter-keyed seeds the
    # host staging path uses, so the math (and resume determinism) is
    # bit-identical to the unfused path.  Requested-but-unusable (no
    # device data / be_verbose) falls back to the per-epoch loop with a
    # warning.  Off by default (dense CPU tier-1 path unchanged).
    fused_rounds: bool = False

    # fused quantized/sparse collectives (ops/packed_reduce.py): keep the
    # compressed client payloads PACKED across the aggregation collective
    # instead of decoding to dense f32 before the psum — q8/q4 run a
    # quantized butterfly/ring reduce-scatter + packed all-gather, topk
    # all-gathers the {idx, val} payloads and scatter-adds once per
    # device.  Requires --compress q8|q4|topk; incompatible with
    # --robust-agg (both replace the aggregation chokepoint).  The dense
    # fused mean is allclose to the unfused reference, NOT bitwise (the
    # wire re-quantizes each hop; tolerance documented in PARITY.md);
    # topk+ADMM falls back to the unfused reduction with a warning (the
    # dual aggregate y + rho*x is dense).  Off by default — the unfused
    # path stays bitwise unchanged.
    fused_collective: bool = False

    # staging/comm overlap (train/engine.py _prestage_round): build and
    # stage round N+1's first epoch (batches + PRNG keys, H2D included)
    # while round N's comm dispatch executes on the device.  Extends
    # prefetch (which only overlaps the host-side shuffle) to the device
    # staging; counter-keyed like prefetch, so kill/resume and the math
    # stay bit-identical on/off.  Off by default; no-op under
    # fused_rounds (one dispatch, nothing to overlap).
    overlap_staging: bool = False

    # whole-round overlap (train/engine.py _predispatch_round): after
    # round N's comm collective is DISPATCHED (async), pre-dispatch
    # round N+1's first train epoch before the host blocks on round N's
    # diagnostics — the device pipeline never drains across the round
    # boundary, hiding the host's record-build/checkpoint/obs work
    # behind device execution.  Counter-keyed exactly like
    # overlap_staging (epoch/key counters advance only when the
    # pre-dispatched epoch is CONSUMED), so trajectories and
    # kill/resume stay bit-identical on/off — only dispatch order
    # changes, never values.  Requested-but-unsafe combinations
    # (fused_rounds, update_guard, async_rounds, faults/churn,
    # campaign, population) warn and fall back to the sequential round
    # loop: each of those reads round N's host-visible outcome before
    # round N+1's inputs are known.  Off by default.
    overlap_round: bool = False

    # sharded server update (parallel/comm.py sharded_federated_mean,
    # arXiv:2004.13336): compute the consensus aggregate via
    # psum_scatter → per-shard divide → all_gather instead of every
    # device reducing the full [N] vector — 1/D of the update FLOPs and
    # reduction memory per chip.  Result is allclose to the replicated
    # mean, not bitwise (different reduction order).  Incompatible with
    # --robust-agg; when fused_collective is also on, the fused path
    # wins (it already divides on the owned shard).  Off by default.
    sharded_update: bool = False

    # buffer donation: pass donate_argnums for the client state and the
    # consensus block vars (z/y/rho/x0/yhat0) on the train/comm/fused
    # round fns so XLA reuses their device buffers in place of fresh
    # allocations.  None = auto: on for TPU/GPU backends, off on CPU
    # (honored there too, but the tests' reference semantics keep inputs
    # alive by default).  Purely an allocator hint — outputs are
    # bit-identical either way.
    donate: Optional[bool] = None

    # checkpointing
    checkpoint_dir: str = "./checkpoints"
    # save a resumable checkpoint after EVERY communication round (params +
    # opt state + ADMM/BB block vars + loop counters + host PRNG); resume
    # with --load-model.  Beyond the reference, which only restarts from its
    # end-of-run s<k>.model files (federated_multi.py:99-103, :226-233)
    midrun_checkpoint: bool = False
    # async mid-run checkpointing: _save_midrun snapshots device state to
    # host without blocking (the D2H copy starts immediately and is
    # materialized before the next round dispatch — donation-safe) and a
    # background writer thread handles serialize + sha256 + slot rotation,
    # with a write barrier on rotation and on run exit.  The on-disk
    # format, slot protocol and corrupt-slot fallback are unchanged; only
    # WHEN the bytes hit disk moves off the round's critical path.
    # Multi-host runs fall back to the synchronous collective save.
    async_checkpoint: bool = False

    # mesh: None -> use as many devices as divide K
    num_devices: Optional[int] = None

    # tracing/profiling (SURVEY.md section 5): when set, the run is wrapped
    # in jax.profiler.trace(profile_dir) producing a TensorBoard/XProf
    # trace with one StepTraceAnnotation("comm_round") per round, keyed on
    # the obs round_index so the trace lines up with the JSONL timeline;
    # per-round wall-clock always lands in history["round_seconds"]
    profile_dir: Optional[str] = None

    # observability (obs/): every run emits schema-versioned telemetry —
    # a run-header event, one validated record per comm round, and a
    # closing summary — through the sinks named here ("auto" resolves to
    # jsonl when obs_dir is set, else none; comma-separable choices:
    # none|jsonl|memory).  Drivers default obs_dir to
    # <checkpoint_dir>/obs so real runs are observable out of the box;
    # "--obs-sinks none" disables file output (emission is host-side at
    # round boundaries either way, so the math is bit-identical).
    # Inspect with: python -m federated_pytorch_test_tpu.obs.report
    obs_dir: Optional[str] = None
    obs_sinks: str = "auto"

    # streaming run-health watchdog (obs/health.py): per-round rules on
    # the SAME values the obs round records already carry (non-finite
    # loss streaks, loss divergence vs an EMA envelope, throughput
    # collapse vs a rolling median, guard/quarantine spikes, async
    # buffer backlog / admission blowups, zero-progress streaks).
    # health_action picks what a trip does: "off" (no monitor at all),
    # "warn" (alert records only — default), "abort" (raise
    # RunHealthAbort), "checkpoint-abort" (force a final verified
    # checkpoint through the existing writers, then raise).  The
    # watchdog only observes — no device syncs, training math
    # bit-identical (tested).
    health_action: str = "warn"
    health_streak: int = 3        # consecutive bad rounds before an alert
    health_window: int = 8        # EMA warm-up / rolling-median window
    health_loss_mult: float = 10.0  # divergence envelope multiplier
    health_tput_frac: float = 0.25  # collapse floor vs rolling median
    # Opt-in early-warning rule: trip on NaN/inf ADMM residuals, which
    # poison the consensus fold one to two rounds before the (staged)
    # loss shows it.  Tripping on the poison round itself is what keeps
    # a clean checkpoint slot alive for the restart supervisor.
    health_residual: bool = False

    # closed-loop control plane (control/): deterministic policy engine
    # over the obs stream + restart supervisor.  control picks the mode:
    # "off" (no controller at all — bit-identical to the uncontrolled
    # path, the default), "observe" (decisions recorded as `control`
    # records, nothing applied), "act" (round/block-scope decisions
    # applied live; checkpoint-then-restart raised to the supervisor).
    # control_policy selects the hysteresis preset (policy.CONTROL_-
    # POLICIES).  Every decision is a pure function of recorded
    # telemetry + round index — replayable bit-exactly via
    # `python -m federated_pytorch_test_tpu.control.replay` (PARITY.md).
    control: str = "off"
    control_policy: str = "default"
    # restart supervisor (control/supervisor.py): on RunHealthAbort /
    # ControlRestart, resume from the last verified checkpoint at most
    # max_restarts times with seeded exponential backoff (base
    # restart_backoff seconds), walking the degradation ladder from the
    # second restart on.  0 = no supervision (default).
    max_restarts: int = 0
    restart_backoff: float = 1.0

    # runtime sanitizers (analysis/sanitize.py) — both default-off, and
    # with both off the engine builds the literal uninstrumented
    # jax.jit(shard_map(...)) chain (bit-identical dense path, same
    # contract as compress/faults/obs):
    # --sanitize runs the train/comm steps under jax.experimental.checkify
    # (NaN/inf + out-of-bounds index assertions; errors throw on the host
    # after each step — a debugging mode, it adds a per-step sync);
    # --retrace-sentinel counts jit (re)traces of the step functions and
    # surfaces cumulative `jit_retraces` in the obs round records so
    # recompilation regressions show up in the perf trajectory.
    sanitize: bool = False
    retrace_sentinel: bool = False

    # compile ledger (obs/costs.py) — default ON: per-jit-site compile
    # wall-seconds and the host seconds inside every instrumented call,
    # drained into the obs round records (`compile_seconds`,
    # `dispatch_seconds`) and `compile` events.  The wrappers only time
    # the dispatch — training math is bit-identical on/off (tested);
    # --no-cost-ledger rebuilds the literal uninstrumented chain.
    cost_ledger: bool = True

    # client-grain flight recorder (obs/clients.py) — default ON: one
    # additive `client` record per communication round (schema v10)
    # with per-client update norms, dist-to-z, loss shares, guard
    # verdicts, fault tags, async staleness/admission, and churn
    # membership, feeding the ClientLedger CLI's anomaly ranking and
    # cohort rollup.  The probe adds two [K_local] norm outputs to the
    # comm/fused programs and host-side list assembly per round; the
    # folded update itself is untouched, and --no-client-ledger
    # rebuilds the literal pre-probe programs (params bitwise
    # identical, tested).
    client_ledger: bool = True
