"""One round kernel for every engine (the engine-unification tentpole).

Every federated engine in this repo — the blockwise classifier engine
(train/engine.py), the VAE trainers layered on it (train/vae_engine.py),
and the rotating-submodel CPC trainer (train/cpc_engine.py) — runs the
same *shape* of communication round:

    stage -> train (local epochs) -> encode (delta + fault tap)
          -> aggregate (mean / robust) -> apply (write-back)

What differs per engine is the compiled middle (loss, optimizer, state
pytrees).  What must NOT differ is the robustness + observability shell
around it: participation sampling, injected faults, update guards +
quarantine, Byzantine-robust aggregation, buffered-async admission,
churn membership, simulated preemption, the client-grain flight
recorder, the health watchdog, and the control plane.  PRs 2-14 built
that shell inside the classifier engine only; this module extracts it
as :class:`RoundKernel`, a mixin every engine composes, so one fault
spec drives one set of seeded draws and one ledger protocol on all
three engines — the classifier-only forks are deleted, not copied.

Refactor contract (tests/test_golden_trajectories.py): with every knob
off, each engine's trajectory is bitwise identical to the pre-kernel
engines — the kernel's fast paths stage the exact arrays the engines
always staged, and the mode flags are STATIC (they flip which programs
are built, so the off state compiles the literal pre-refactor chain).

Host-class contract (the engine plugin surface the mixin reads):

========================  =============================================
``self.cfg``              a :class:`~.config.FederatedConfig` (or a
                          dataclass with the same robustness fields)
``self.algo``             strategy object with ``.name`` /
                          ``.communicates`` (train/algorithms.py)
``self.mesh`` ``self.D``  the client mesh and its device count
``self.obs_engine``       engine tag for obs records
``self.obs_run_name``     optional run-name override (drivers set it)
``self._ckpt_writer``     async checkpoint writer or None
``round_bytes_on_wire``   ``(N, n_clients) -> int`` wire-byte model
``_save_midrun``          ``(path, state, blockvars, nxt, history)``
                          (only reached from ``_health_abort``)
``_init_comp_state``      per-block compressor state init (only
                          reached from ``_reset_comp_rows``; engines
                          without a compression path never call it)
========================  =============================================
"""

from __future__ import annotations

import os
import time
import uuid
from typing import Optional

import jax
import numpy as np

from federated_pytorch_test_tpu.parallel.mesh import (
    client_sharding,
    fetch,
    replicated_sharding,
    stage_global,
)
from federated_pytorch_test_tpu.train.faults import FaultSpec


#: the parts of a ``block_switch`` span, in the order the engine runs
#: (and stamps) them
BLOCK_SWITCH_PARTS = ("build_fns", "block_size", "block_vars", "init_opt")


class RoundKernel:
    """Mixin: the engine-agnostic slice of a communication round.

    Everything here is HOST-side machinery — seeded mask draws, ledger
    bookkeeping, checkpoint meta, obs emission.  The jitted middle of
    the round stays in the engine; the kernel hands it the activity /
    corruption / guard-bound arrays and takes the verdicts back.
    """

    # ------------------------------------------------------------------
    # construction: ledgers, fault layer, staged constants, validation
    # ------------------------------------------------------------------
    def _init_round_kernel(self) -> None:
        """Construct the fault layer + every host-side round ledger.

        Call once from the engine's ``__init__`` after ``self.cfg`` is
        set (and before any validation that reads ``self.faults``).
        """
        from federated_pytorch_test_tpu.parallel.comm import make_robust_mean

        cfg = self.cfg
        # fault injection + robust aggregation validate at construction,
        # not mid-run inside jit
        self.faults = FaultSpec.parse(cfg.fault_spec)
        # soak campaign (campaign/): the trace-driven schedule that owns
        # the fault families per round.  _campaign_tick swaps self.faults
        # for the window's derived spec at every round entry; the parsed
        # base (the disabled spec — campaign and fault_spec are mutually
        # exclusive) keeps mode/scale/clients defaults.  None = off, the
        # literal seed path.  The floor is the resume re-fire guard for
        # deterministic preempt_at events (same role _preempt_armed
        # plays for the Bernoulli preempt= family); the last-emitted
        # hour drives transition-only `campaign` record emission.
        from federated_pytorch_test_tpu.campaign.schedule import (
            CampaignSchedule)
        self.campaign = CampaignSchedule.parse(
            getattr(cfg, "campaign_spec", "none"))
        self._campaign_base_faults = self.faults
        self._campaign_floor = -1
        self._campaign_window = None
        self._campaign_last_hour = None
        # serving plane (serve/): batched online inference over the
        # consensus state at every round boundary.  The parsed schedule
        # owns the PURE per-round plan — traffic draw (tag 83), batch
        # plan, weights_version = 1 + r // swap_every, drift injection —
        # all functions of (seed, round_index) alone, so control.replay
        # re-derives every pure `serve` field from the header config and
        # NO serve state rides in the checkpoint meta (a resumed segment
        # republishes the round's version on its first tick).  None =
        # serving off, the literal seed path (bitwise; golden-gated).
        # The runtime half (predictor, hot-swap buffer, eval stream) is
        # built lazily at the first serving round via the engine's
        # _build_serve_plane hook; _serve_forced is the control plane's
        # pending forced-refresh flag (serve_swap interventions).
        from federated_pytorch_test_tpu.serve.batcher import ServeSchedule
        self._serve_sched = ServeSchedule.parse(
            getattr(cfg, "serve_spec", "none"))
        self._serve_plane = None
        self._serve_forced = False
        self.mean_fn = make_robust_mean(cfg.robust_agg,
                                        trim_frac=cfg.trim_frac,
                                        clip_mult=cfg.clip_mult)
        # host-side fault-tolerance state: per-client remaining quarantine
        # rounds and the per-block running guard norm scale (inf = not yet
        # calibrated; no norm bound until one clean round has been seen).
        # Both ride in the mid-run checkpoint meta so resume replays them.
        self._quarantine = np.zeros(cfg.K, np.int64)
        self._guard_scale = float("inf")
        # client-ledger staging area (obs/clients.py): the activity/
        # guard paths stash this round's per-client HOST arrays here and
        # _emit_client_record folds them into one `client` record —
        # advisory telemetry only, never read by the math
        self._client_round: dict = {}
        # host timeline outside the round windows (schema v15): spans
        # already bounded and waiting for the next emission, and the
        # tail of the round that is still running its bookkeeping
        self._outer_marks: list = []
        self._tail_open: Optional[tuple] = None
        # buffered-async staleness ledger (cfg.async_rounds): per-client
        # scheduled arrival round (-1 = nothing in flight) and dispatch
        # round of the in-flight update, plus the cumulative admission-
        # rejection count.  Host state like the quarantine ledger — it
        # rides in the mid-run checkpoint meta so a resumed run replays
        # the identical arrival schedule (_round_activity_async).
        self._async_arrival = np.full(cfg.K, -1, np.int64)
        self._async_birth = np.zeros(cfg.K, np.int64)
        self._async_rejected = 0
        # elastic-federation state: the [K] bool churn membership ledger
        # (everyone present at start; join=/leave= fault families advance
        # it once per round in _round_activity) and the one-shot arming
        # flag for simulated preemption (preempt= draws are deterministic
        # in the round coordinates, so a resumed segment must disarm them
        # or the same round would re-fire forever).  The ledger rides in
        # the mid-run checkpoint meta like the quarantine/async ledgers.
        self._members = np.ones(cfg.K, bool)
        self._rejoined_mask = np.zeros(cfg.K, bool)
        self._members_joined = 0
        self._members_left = 0
        self._preempt_armed = True
        # population federation (population/): cfg.population registered
        # virtual clients, cfg.K device slots.  The registry keeps the
        # [population] ledgers; every round _population_round_begin
        # scatters the previous cohort's slot rows back and gathers the
        # new cohort's rows into the SAME [K] slot arrays above, so the
        # whole robustness shell runs unchanged over slots.  None when
        # population is off; an identity registry (population == K) is
        # constructed but inert — every branch below checks
        # ``not identity``, which is the bitwise K=D contract.
        self._registry = None
        self._cohort = None                  # this round's sorted rids
        self._pop_slot_mask = None           # control-plane cohort mask
        self._cohort_frac = float(getattr(cfg, "cohort_frac", 1.0))
        self._pop_comp_prev = None           # cohort owning state.comp rows
        pop = int(getattr(cfg, "population", 0))
        if pop:
            from federated_pytorch_test_tpu.population import ClientRegistry
            self._registry = ClientRegistry(
                pop, cfg.K, cfg.seed,
                sampling=getattr(cfg, "cohort_sampling", "uniform"))

    @property
    def _churn_live(self) -> bool:
        """Can THIS run's membership ledger ever move?  True for a
        static join=/leave= fault family and for any campaign whose
        schedule carries churn — sticky across windows, because the
        ledger meta, the rejoin resets and the v9 round fields must not
        flap when a campaign window happens to zero the churn
        probabilities (a resume from such a window would otherwise lose
        the ledger)."""
        return (self.faults.churn_enabled
                or (self.campaign is not None and self.campaign.has_churn))

    @property
    def _pop_active(self) -> bool:
        """Population mode live (registered clients ≫ cohort)?  False for
        both population-off and the identity registry, so every guarded
        branch degenerates to the literal pre-population code."""
        return self._registry is not None and not self._registry.identity

    def _stage_round_constants(self) -> None:
        """Stage the per-run constant masks once (call after the mesh
        exists).  The train/comm signatures take the per-round activity
        vector, the corruption vector and the replicated guard bound
        unconditionally (uniform shard_map specs); on the default path
        all three are these constants and the traced program never
        reads them (numerics unchanged)."""
        csh = client_sharding(self.mesh)
        rsh = replicated_sharding(self.mesh)
        self._ones_mask = stage_global(
            np.ones(self.cfg.K, np.float32), csh)
        self._zero_corrupt = stage_global(
            np.zeros(self.cfg.K, np.float32), csh)
        self._inf_bound = stage_global(
            np.asarray(np.inf, np.float32), rsh)

    def _validate_round_cfg(self) -> None:
        """Construction-time validation of the shared robustness /
        health / control knobs — a bad flag combination fails at
        construction, not mid-run inside jit."""
        cfg = self.cfg
        if cfg.bb_update and (self.faults.enabled or cfg.update_guard):
            raise ValueError(
                "fault injection / update guards are incompatible with "
                "bb_update: both can mask clients out of a round, and the "
                "BB spectral history (x0/yhat0 deltas) assumes every "
                "client moves every round (consensus_multi.py:242-278)")
        if self.campaign is not None:
            if self.faults.enabled:
                raise ValueError(
                    "campaign_spec and fault_spec are mutually exclusive: "
                    "the campaign schedule OWNS the fault families' "
                    "probabilities per round (fold static fault knobs "
                    "into the campaign spec instead)")
            if cfg.bb_update:
                raise ValueError(
                    "campaign_spec is incompatible with bb_update: the "
                    "campaign's arrival/fault windows mask clients out "
                    "of rounds, and the BB spectral history assumes "
                    "every client moves every round "
                    "(consensus_multi.py:242-278)")
        if cfg.async_rounds:
            if cfg.bb_update:
                raise ValueError(
                    "async_rounds is incompatible with bb_update: the BB "
                    "spectral history assumes every client moves in "
                    "lockstep rounds (consensus_multi.py:242-278)")
            if cfg.max_staleness < 0:
                raise ValueError(
                    f"max_staleness={cfg.max_staleness} must be >= 0")
            if cfg.staleness_alpha < 0:
                raise ValueError(
                    f"staleness_alpha={cfg.staleness_alpha} must be >= 0")
        if cfg.quarantine_rounds < 0:
            raise ValueError(
                f"quarantine_rounds={cfg.quarantine_rounds} must be >= 0")
        pop = int(getattr(cfg, "population", 0))
        if pop < 0:
            raise ValueError(f"population={pop} must be >= 0 (0 = off)")
        if pop:
            if pop < cfg.K:
                raise ValueError(
                    f"population={pop} must be >= K={cfg.K}: the cohort "
                    "fills every device slot each round (use "
                    "population=0 to turn virtualization off)")
            if cfg.bb_update and pop != cfg.K:
                raise ValueError(
                    "population sampling is incompatible with bb_update: "
                    "the BB spectral history assumes the SAME clients "
                    "move every round (consensus_multi.py:242-278), and "
                    "a rotating cohort re-seats the [K] slots")
            from federated_pytorch_test_tpu.population.sampler import (
                SAMPLER_CHOICES)
            if getattr(cfg, "cohort_sampling",
                       "uniform") not in SAMPLER_CHOICES:
                raise ValueError(
                    f"cohort_sampling={cfg.cohort_sampling!r} must be "
                    f"one of {SAMPLER_CHOICES}")
        frac = float(getattr(cfg, "cohort_frac", 1.0))
        if not 0.0 < frac <= 1.0:
            raise ValueError(
                f"cohort_frac={frac} must be in (0, 1]")
        from federated_pytorch_test_tpu.obs.health import HEALTH_ACTIONS
        if cfg.health_action not in HEALTH_ACTIONS:
            raise ValueError(
                f"health_action={cfg.health_action!r} must be one of "
                f"{HEALTH_ACTIONS}")
        if cfg.health_streak < 1:
            raise ValueError(
                f"health_streak={cfg.health_streak} must be >= 1")
        if cfg.health_window < 2:
            raise ValueError(
                f"health_window={cfg.health_window} must be >= 2")
        if cfg.health_loss_mult <= 1 or cfg.health_tput_frac <= 0:
            raise ValueError(
                "health_loss_mult must be > 1 and health_tput_frac > 0 "
                f"(got {cfg.health_loss_mult}, {cfg.health_tput_frac})")
        if cfg.guard_norm_mult <= 0:
            raise ValueError(
                f"guard_norm_mult={cfg.guard_norm_mult} must be positive")
        from federated_pytorch_test_tpu.control.policy import (
            CONTROL_MODES, CONTROL_POLICIES)
        if cfg.control not in CONTROL_MODES:
            raise ValueError(
                f"control={cfg.control!r} must be one of {CONTROL_MODES}")
        if cfg.control_policy not in CONTROL_POLICIES:
            raise ValueError(
                f"control_policy={cfg.control_policy!r} must be one of "
                f"{CONTROL_POLICIES}")
        if cfg.max_restarts < 0:
            raise ValueError(
                f"max_restarts={cfg.max_restarts} must be >= 0")
        if cfg.restart_backoff < 0:
            raise ValueError(
                f"restart_backoff={cfg.restart_backoff} must be >= 0")
        if cfg.barrier_timeout < 0:
            raise ValueError(
                f"barrier_timeout={cfg.barrier_timeout} must be >= 0 "
                "(0 disables the bounded wait)")
        if cfg.barrier_timeout > 0:
            from federated_pytorch_test_tpu.parallel.mesh import (
                configure_barrier_timeout)
            configure_barrier_timeout(cfg.barrier_timeout)

    # ------------------------------------------------------------------
    # per-round activity: participation x quarantine x faults x churn
    # ------------------------------------------------------------------
    def _participation_host(self, nloop: int, ci: int, nadmm: int):
        """Host [K] f32 participation draw for this round — STATELESSLY
        keyed on the round coordinates, so a resumed run redraws the
        identical masks — with at least one participant guaranteed.

        Under population mode the Bernoulli is drawn per REGISTRY id
        (the whole [population] vector, then the cohort's rows), so
        whether client rid participates is a property of rid and the
        round, not of which slot it landed in — and the population == K
        identity cohort (``arange(K)``) reads back the exact seed-path
        vector."""
        rng = np.random.default_rng(
            [self.cfg.seed, 11, nloop, ci, nadmm])
        if self._pop_active:
            mP = (rng.random(self._registry.population)
                  < self.cfg.participation).astype(np.float32)
            m = mP[self._cohort]
        else:
            m = (rng.random(self.cfg.K)
                 < self.cfg.participation).astype(np.float32)
        if not m.any():
            m[int(rng.integers(self.cfg.K))] = 1.0
        return m

    def _population_round_begin(self, nloop: int, ci: int,
                                nadmm: int) -> None:
        """Rotate the registry cohort for this round (population mode).

        Scatters the PREVIOUS cohort's slot ledgers back to their
        registry rows, draws this round's cohort (a pure function of the
        seed + round coordinates — sampler.py), and gathers the new
        cohort's rows into the same [K] slot arrays the whole robustness
        shell already runs over.  The round clock for the async
        late-arrival clamp is ``nadmm`` (the within-block round index
        the arrival schedule is expressed in)."""
        if not self._pop_active:
            return
        reg = self._registry
        if self._cohort is not None:
            reg.scatter_ledgers(self._cohort, quarantine=self._quarantine,
                                members=self._members,
                                arrival=self._async_arrival,
                                birth=self._async_birth)
        ids, mask = reg.draw(nloop, ci, nadmm, self._cohort_frac)
        led = reg.gather_ledgers(ids, nadmm)
        self._cohort = ids
        self._pop_slot_mask = mask
        self._quarantine = led["quarantine"]
        self._members = led["members"]
        self._async_arrival = led["arrival"]
        self._async_birth = led["birth"]

    def _round_faults_cohort(self, nloop: int, ci: int, nadmm: int):
        """This round's (drop, straggle, corrupt) [K] vectors.

        Population mode draws the whole [population] fault vectors and
        takes the cohort's rows — a fault is a property of the REGISTRY
        client, not the slot it landed in, so `clients=` fault selectors
        name registry ids and the identity cohort reads back the exact
        seed-path draw (bitwise K=D contract)."""
        faults = self.faults
        if self._pop_active:
            dP, sP, cP = faults.round_faults(
                self._registry.population, nloop, ci, nadmm)
            c = self._cohort
            return dP[c], sP[c], cP[c]
        return faults.round_faults(self.cfg.K, nloop, ci, nadmm)

    def _round_mask(self, nloop: int, ci: int, nadmm: int):
        """[K] f32 activity mask for this communication round.

        Full participation (the default, reference parity) returns the
        staged ones mask; under ``cfg.participation < 1`` the stateless
        per-round draw (``_participation_host``).
        """
        if self.cfg.participation >= 1.0:
            return self._ones_mask
        return stage_global(self._participation_host(nloop, ci, nadmm),
                            client_sharding(self.mesh))

    @property
    def _client_probe(self) -> bool:
        """Client-grain flight recorder live? (cfg.client_ledger,
        obs/clients.py) — static: flips which comm/fused programs are
        BUILT, so the off state is the literal pre-probe chain."""
        return bool(getattr(self.cfg, "client_ledger", True)) \
            and self.algo.communicates

    def _round_activity(self, nloop: int, ci: int, nadmm: int):
        """Compose participation sampling x quarantine x injected faults
        into this round's activity masks.

        Returns ``(train, comm, corrupt, comm_host, counts)``:

        - ``train``  [K] staged: clients that run local epochs this round
          (stragglers are in ``comm`` but not here — they ship their
          round-start params, i.e. the promised update is withheld);
        - ``comm``   [K] staged: clients in the exchange (dropped and
          quarantined clients are out of BOTH — exactly the established
          non-participant semantics);
        - ``corrupt`` [K] staged: 1 where the shipped delta is poisoned
          (only ever a subset of ``comm``);
        - ``comm_host``: the host copy of ``comm`` (the guard's
          quarantine bookkeeping needs it to tell "active and rejected"
          from "never participated");
        - ``counts``: host ints for the history record (``n_comm`` plus
          ``fault_*`` when injection is live; empty on the fast path).

        The fast path (no faults, nothing quarantined) returns the staged
        participation mask untouched — the reference-parity round stages
        the exact arrays it always did.

        Under ``cfg.async_rounds`` the buffered-async scheduler takes
        over (``_round_activity_async``): ``comm`` then carries the
        round's FRACTIONAL staleness weights instead of a 0/1 mask.
        """
        cfg, faults = self.cfg, self.faults
        # population mode: rotate the registry cohort FIRST — every
        # ledger the tick/draws below read is a cohort slot view
        self._population_round_begin(nloop, ci, nadmm)
        # the churn ledger ticks exactly once per round, BEFORE the async
        # delegation, so both schedulers see the same membership
        churn_counts = self._membership_tick(nloop, ci, nadmm)
        if cfg.async_rounds:
            return self._round_activity_async(nloop, ci, nadmm,
                                              churn_counts)
        quarantined = int(np.sum(self._quarantine > 0))
        if (not faults.enabled and quarantined == 0
                and self._pop_slot_mask is None and self.campaign is None):
            if cfg.participation >= 1.0:
                dev, host = self._ones_mask, np.ones(cfg.K, np.float32)
            else:
                host = self._participation_host(nloop, ci, nadmm)
                dev = stage_global(host, client_sharding(self.mesh))
            if self._client_probe:
                self._client_round = {"active": host, "weight": host}
            return dev, dev, self._zero_corrupt, host, {}
        base = (np.ones(cfg.K, np.float32) if cfg.participation >= 1.0
                else self._participation_host(nloop, ci, nadmm))
        if self._pop_slot_mask is not None:
            # control-plane cohort rung: inactive slots sit the round
            # out entirely (same non-participant semantics as sampling)
            base = base * self._pop_slot_mask
        if self._churn_live:
            # a departed client is out of the round entirely — not
            # sampled, not faulted, not counted; the mean renormalizes
            # over live members through the usual psum(w) denominator
            base = base * self._members.astype(np.float32)
        ok = 1.0 - (self._quarantine > 0).astype(np.float32)
        drop = straggle = corrupt = np.zeros(cfg.K, np.float32)
        if faults.enabled:
            drop, straggle, corrupt = self._round_faults_cohort(
                nloop, ci, nadmm)
        comm = base * ok * (1.0 - drop)
        train = comm * (1.0 - straggle)
        corrupt = corrupt * comm
        counts = {"n_comm": int(comm.sum())}
        if faults.enabled:
            counts.update(
                fault_dropped=int(np.sum(base * ok * drop)),
                fault_straggled=int(np.sum(comm * straggle)),
                fault_corrupted=int(np.sum(corrupt)))
        counts.update(churn_counts)
        if self._client_probe:
            self._client_round = {
                "active": comm, "weight": comm,
                "quarantine": self._quarantine.copy(),   # round-start census
                "dropped": base * ok * drop,
                "straggled": comm * straggle,
                "corrupted": corrupt,
            }
            if self._churn_live:
                self._client_round["members"] = \
                    self._members.astype(np.float32)
        csh = client_sharding(self.mesh)
        return (stage_global(train, csh), stage_global(comm, csh),
                stage_global(corrupt, csh), comm, counts)

    def _membership_tick(self, nloop: int, ci: int, nadmm: int) -> dict:
        """Advance the churn membership ledger by one round.

        Pure bookkeeping around ``FaultSpec.round_churn`` (the seeded
        draw): departed clients have their quarantine sentence voided
        and any in-flight async update dropped (the update's sender no
        longer exists); rejoining clients get their compressor/EF rows
        re-initialized by the round loop (``_rejoined_mask``) — a
        returning client is a NEW client with the current server state,
        not a ghost resuming a stale residual.  Returns the round-record
        counts (empty when churn is off, keeping v8 records byte-
        identical)."""
        faults = self.faults
        if not self._churn_live:
            return {}
        if self._pop_active:
            # population mode ticks the WHOLE registry roster: churn is
            # a property of registry clients, sampled or not, so the
            # membership trajectory is independent of the cohort draw.
            # The slot views refresh from the registry rows afterwards
            # (a departed cohort member leaves mid-round like any other
            # departure; the gather's late-arrival clamp is idempotent).
            reg = self._registry
            prevP = reg.members.copy()
            newP = faults.round_churn(prevP, nloop, ci, nadmm)
            joinedP = newP & ~prevP
            leftP = prevP & ~newP
            reg.members = newP
            if leftP.any():
                reg.quarantine[leftP] = 0
                reg.async_arrival[leftP] = -1
                reg.async_birth[leftP] = 0
                reg.drop_comp_rows(leftP)
            c = self._cohort
            led = reg.gather_ledgers(c, nadmm)
            self._quarantine = led["quarantine"]
            self._members = led["members"]
            self._async_arrival = led["arrival"]
            self._async_birth = led["birth"]
            self._rejoined_mask = joinedP[c]
            self._members_joined += int(joinedP.sum())
            self._members_left += int(leftP.sum())
            return {"members_active": int(newP.sum()),
                    "joined": int(joinedP.sum()),
                    "left": int(leftP.sum())}
        prev = self._members
        self._members = faults.round_churn(prev, nloop, ci, nadmm)
        joined = self._members & ~prev
        left = prev & ~self._members
        if left.any():
            self._quarantine[left] = 0
            self._async_arrival[left] = -1
            self._async_birth[left] = 0
        self._rejoined_mask = joined
        self._members_joined += int(joined.sum())
        self._members_left += int(left.sum())
        return {"members_active": int(self._members.sum()),
                "joined": int(joined.sum()),
                "left": int(left.sum())}

    def _campaign_tick(self, rounds_done: int, nloop: int, ci: int,
                       nadmm: int, checkpoint_path) -> None:
        """Apply the campaign schedule's window for round ``rounds_done``.

        Swaps ``self.faults`` for the window's derived spec — every
        probability then flows through the EXISTING seeded families
        (tags 47/67) with the campaign seed — and stashes the window for
        ``_emit_round_obs``'s transition-only ``campaign`` record.  A
        deterministic ``preempt_at`` event raises
        :class:`CollectiveTimeoutError` exactly like the Bernoulli
        ``preempt=`` family, after the newest checkpoint is durable;
        ``_campaign_floor`` (the resumed segment's starting round) keeps
        the deterministic event from re-firing forever on resume —
        the same one-shot contract ``_preempt_armed`` gives tag 71.
        """
        if self.campaign is None:
            return
        w = self.campaign.window(rounds_done)
        self.faults = self.campaign.spec_for(
            w, base=self._campaign_base_faults)
        self._campaign_window = w
        if (w.preempt_now and rounds_done > self._campaign_floor
                and rounds_done > 0 and checkpoint_path is not None):
            if self._ckpt_writer is not None:
                self._ckpt_writer.wait()
            from federated_pytorch_test_tpu.parallel.mesh import (
                CollectiveTimeoutError)
            raise CollectiveTimeoutError(
                f"campaign preemption at round {rounds_done} "
                f"(virtual hour {w.hour}): campaign spec preempt_at "
                f"scheduled this round", round_index=rounds_done)

    def _emit_campaign_record(self, obs, round_index: int) -> None:
        """Transition-only ``campaign`` record emission: the segment's
        first completed round, every virtual-hour boundary, and any
        post-resume re-run of a preempted round — the exact rule
        ``CampaignSchedule.expected_emissions`` re-derives for
        ``control.replay``.  Emitted right AFTER the round record it
        rides with (file order == replay order)."""
        w = self._campaign_window
        if w is None or w.round_index != round_index:
            return
        if (self._campaign_last_hour is None
                or w.hour != self._campaign_last_hour or w.preempt_now):
            obs.campaign_event(self.campaign.record_fields(w))
        self._campaign_last_hour = w.hour

    # ------------------------------------------------------------------
    # serving plane (serve/): hot-swap + traffic + the `serve` record
    # ------------------------------------------------------------------
    def _build_serve_plane(self, sched) -> dict:
        """Engine hook: build the serving runtime for this engine — a
        dict with the bucketed jitted predictor, the hot-swap buffer,
        the micro-batcher, the host traffic pool and (classifier-shaped
        engines) the eval stream.  The base kernel has no model surface
        to serve; engines that do (train/engine.py) override."""
        raise ValueError(
            f"serve_spec is set but the {self.obs_engine!r} engine has "
            "no serving adapter (_build_serve_plane); serve with the "
            "classifier/VAE engines, or use serve.infer heads directly")

    def _serve_export(self, state):
        """Engine hook: the served consensus weights for the current
        client state (overridden next to ``_build_serve_plane``)."""
        raise ValueError(
            f"the {self.obs_engine!r} engine has no serving adapter")

    def _serve_tick(self, obs, round_index: int, state, log=print) -> None:
        """One serving round, ridden at the round-obs boundary.

        Order of operations: publish (when the schedule's pure swap
        sequence says this round starts a new ``weights_version``, or a
        control-plane forced refresh is pending), then answer the
        round's seeded traffic through the micro-batcher, then score the
        answers on the eval stream and emit ONE additive ``serve``
        record (schema v13).  The pure fields all come from the
        schedule; latency/gap/accuracy numbers are advisory.  A forced
        refresh republishes the CURRENT consensus without bumping the
        version, so interventions never perturb the replay-checked swap
        sequence."""
        sched = self._serve_sched
        forced = self._serve_forced
        self._serve_forced = False
        if self._serve_plane is None:
            self._serve_plane = self._build_serve_plane(sched)
        plane = self._serve_plane
        fields = sched.record_fields(round_index)
        version = int(fields["weights_version"])
        gap = None
        if plane["buffer"].version != version or forced:
            gap = plane["buffer"].publish(version, self._serve_export(state),
                                          block=True)
        # request content: pool rows drawn on the tag-83 content
        # substream — deterministic, but advisory (replay checks the
        # COUNT, which is the schedule's requests_for draw)
        n = int(fields["requests"])
        rng = np.random.default_rng([sched.seed, 83, round_index, 2])
        idx = rng.integers(plane["pool_n"], size=n)
        _, served = plane["buffer"].acquire()
        plane["current"] = served       # snapshot for the whole drain:
        mb = plane["batcher"]           # never-torn even if a publish
        pool_x = plane["pool_x"]        # landed mid-round
        for i in idx:
            mb.submit(pool_x[i])
        outs, tel = mb.drain()
        rec = dict(fields)
        rec["serve_p50_ms"] = round(tel["serve_p50_ms"], 6)
        rec["serve_p99_ms"] = round(tel["serve_p99_ms"], 6)
        rec["serve_qps"] = round(tel["serve_qps"], 6)
        if gap is not None:
            rec["swap_gap_seconds"] = round(gap, 6)
        if forced:
            rec["forced_refresh"] = True
            log(f"serve: forced refresh applied at round {round_index} "
                f"(version {version} republished)")
        stream = plane.get("stream")
        if stream is not None and plane.get("pool_y") is not None:
            rec.update(stream.score(round_index, np.stack(outs),
                                    plane["pool_y"][idx]))
        obs.serve_event(rec)

    def _maybe_preempt(self, nloop: int, ci: int, nadmm: int,
                       rounds_done: int, checkpoint_path) -> None:
        """Simulated slice preemption (fault family ``preempt=``).

        Raises :class:`CollectiveTimeoutError` — the same type a real
        hung collective produces under the bounded wait — so the restart
        supervisor's reshape rung exercises identically for simulated
        and genuine preemptions.  Fires only when armed (fresh segments:
        the draw is deterministic in the round coordinates, so a resumed
        segment replaying this round must not re-fire), only after at
        least one round has checkpointed (there must be a recovery
        point), and after the async writer has made that checkpoint
        durable."""
        faults = self.faults
        if (faults.preempt <= 0.0 or not self._preempt_armed
                or rounds_done == 0 or checkpoint_path is None):
            return
        if not faults.round_preempt(nloop, ci, nadmm):
            return
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait()
        from federated_pytorch_test_tpu.parallel.mesh import (
            CollectiveTimeoutError)
        raise CollectiveTimeoutError(
            f"simulated preemption at round {rounds_done} "
            f"(nloop={nloop}, block={ci}, nadmm={nadmm}): fault spec "
            f"preempt={faults.preempt} drew this round",
            round_index=rounds_done)

    def _reset_comp_rows(self, comp, ci: int, mask: np.ndarray):
        """Re-initialize the compressor/EF state rows of rejoining
        clients to this block's fresh init (leaves whose leading axis is
        not the client stack pass through untouched)."""
        import jax.numpy as jnp

        fresh = self._init_comp_state(ci)
        m = stage_global(mask.astype(np.float32),
                         client_sharding(self.mesh))

        def sel(cur, new):
            if getattr(cur, "ndim", 0) == 0 or cur.shape[0] != self.cfg.K:
                return cur
            mm = m.reshape((-1,) + (1,) * (cur.ndim - 1))
            return jnp.where(mm > 0, new, cur)

        return jax.tree.map(sel, comp, fresh)

    def _round_activity_async(self, nloop: int, ci: int, nadmm: int,
                              churn_counts: Optional[dict] = None):
        """Buffered-async round schedule (cfg.async_rounds).

        The server stops barriering: a free client sampled this round
        DISPATCHES — it runs its local epochs now and its update spends
        ``faults.round_delays`` rounds in transit (the frozen client
        params ARE the in-flight buffer; the client is masked out of
        train AND comm until delivery, so there is exactly one
        outstanding update per client).  Deliveries scheduled for this
        round pass the bounded-staleness admission controller
        (``staleness <= cfg.max_staleness``, rejects discarded and
        counted) and join the exchange with polynomially decayed weights
        ``w = (1 + s)^(-staleness_alpha)`` — exactly 1.0 at staleness 0,
        so a no-delay async run aggregates like the synchronous path.

        Same return contract as ``_round_activity`` except ``comm`` /
        ``comm_host`` carry the fractional admission weights and
        ``counts`` gains the async telemetry (``async_arrived``,
        ``admission_rejected``, ``buffer_depth``, ``staleness_hist``).
        Every draw is stateless in the round coordinates and the ledger
        rides in the checkpoint meta, so fresh runs and mid-run resumes
        replay bit-identically.  Updates still in flight when the block
        rotates are void (the flat block vector changes meaning) — the
        ledger resets with the block, like the guard scale.
        """
        cfg, faults = self.cfg, self.faults
        K = cfg.K
        base = (np.ones(K, np.float32) if cfg.participation >= 1.0
                else self._participation_host(nloop, ci, nadmm))
        if self._pop_slot_mask is not None:
            # cohort rung: an inactive slot neither dispatches nor has
            # anything in flight voided — its ledger rows just sit
            base = base * self._pop_slot_mask
        if self._churn_live:
            # departed clients neither dispatch nor deliver (the
            # membership tick already voided their in-flight slots)
            base = base * self._members.astype(np.float32)
        ok = 1.0 - (self._quarantine > 0).astype(np.float32)
        drop = straggle = corrupt = np.zeros(K, np.float32)
        if faults.enabled:
            drop, straggle, corrupt = self._round_faults_cohort(
                nloop, ci, nadmm)
        free = (self._async_arrival < 0).astype(np.float32)
        # dispatchers: free clients sampled this round that didn't drop.
        # A straggler still dispatches — its training is withheld, so the
        # update in flight is its round-start params (the sync stale-
        # update semantics, now also late).
        dispatch = base * ok * (1.0 - drop) * free
        train = dispatch * (1.0 - straggle)
        if self._pop_active:
            # transit delays are a property of the registry client's
            # link (the per-rid heterogeneity stream), not of the slot
            delays = faults.round_delays(
                self._registry.population, nloop, ci, nadmm)[self._cohort]
        else:
            delays = faults.round_delays(K, nloop, ci, nadmm)
        d_idx = dispatch > 0
        self._async_arrival[d_idx] = nadmm + delays[d_idx]
        self._async_birth[d_idx] = nadmm
        # deliveries scheduled for THIS round (a delay-0 dispatch arrives
        # in its own round — the synchronous limit)
        arrive = self._async_arrival == nadmm
        stale = np.where(arrive, nadmm - self._async_birth, 0)
        admit = arrive & (stale <= cfg.max_staleness)
        reject = arrive & ~admit
        w = np.zeros(K, np.float32)
        w[admit] = (1.0 + stale[admit]) ** (-cfg.staleness_alpha)
        # every delivery retires its slot — admitted or rejected, the
        # client is free to be sampled again next round
        self._async_arrival[arrive] = -1
        self._async_rejected += int(reject.sum())
        # corruption poisons the wire at DELIVERY time (the encode
        # boundary runs when the server ingests the update)
        corrupt = corrupt * admit.astype(np.float32)
        hist = np.bincount(stale[admit].astype(np.int64),
                           minlength=cfg.max_staleness + 1)
        counts = {
            "n_comm": int(admit.sum()),
            "async_arrived": int(arrive.sum()),
            "admission_rejected": int(reject.sum()),
            "buffer_depth": int(np.sum(self._async_arrival >= 0)),
            "staleness_hist": [int(c) for c in hist],
        }
        if faults.enabled:
            counts.update(
                fault_dropped=int(np.sum(base * ok * free * drop)),
                fault_straggled=int(np.sum(dispatch * straggle)),
                fault_corrupted=int(np.sum(corrupt)))
        counts.update(churn_counts or {})
        if self._client_probe:
            self._client_round = {
                "active": admit.astype(np.float32), "weight": w.copy(),
                "quarantine": self._quarantine.copy(),
                "dropped": base * ok * free * drop,
                "straggled": dispatch * straggle,
                "corrupted": corrupt,
                # -1 = no arrival this round; rejects show up as
                # staleness >= 0 with admitted == 0 (obs/clients.py)
                "staleness": np.where(arrive, stale, -1).astype(np.int64),
                "admitted": admit.astype(np.float32),
            }
            if self._churn_live:
                self._client_round["members"] = \
                    self._members.astype(np.float32)
        csh = client_sharding(self.mesh)
        return (stage_global(train, csh), stage_global(w, csh),
                stage_global(corrupt, csh), w, counts)

    # ------------------------------------------------------------------
    # update guard: norm bound, verdicts, quarantine
    # ------------------------------------------------------------------
    def _round_gbound(self):
        """Staged replicated norm bound for the update guard: no bound
        (+inf) until one accepted round has calibrated the running scale
        — a fresh block's deltas have no reference magnitude yet."""
        if not (self.cfg.update_guard and np.isfinite(self._guard_scale)):
            return self._inf_bound
        return stage_global(
            np.asarray(self.cfg.guard_norm_mult * self._guard_scale,
                       np.float32), replicated_sharding(self.mesh))

    def _apply_guard_verdicts(self, diag, okf, comm_host) -> None:
        """Host-side guard aftermath, shared by the fused and unfused
        round paths: quarantine this round's offenders (active AND
        rejected — okf alone cannot tell a rejected client from one that
        never participated), tick running sentences down one round, and
        fold the accepted delta-norm scale into the guard bound (EMA;
        the first clean round seeds it)."""
        cfg = self.cfg
        okf_h = np.asarray(fetch(okf))
        tripped = (comm_host > 0) & (okf_h < 0.5)
        if self._client_probe:
            self._client_round["guard_ok"] = okf_h
        self._quarantine = np.maximum(self._quarantine - 1, 0)
        if cfg.quarantine_rounds > 0:
            self._quarantine[tripped] = cfg.quarantine_rounds
        if self._pop_active:
            # advisory registry counters (telemetry only); the slot
            # quarantine above scatters back at the next cohort rotation
            self._registry.note_round(self._cohort, comm_host, tripped)
        if diag.get("n_ok", 0.0) > 0:
            nm = diag["guard_norm_mean"]
            self._guard_scale = (
                nm if not np.isfinite(self._guard_scale)
                else 0.5 * self._guard_scale + 0.5 * nm)

    # ------------------------------------------------------------------
    # ledger checkpoint meta: one protocol for every engine
    # ------------------------------------------------------------------
    def _ledger_meta(self) -> dict:
        """The kernel's slice of the mid-run checkpoint meta: mesh
        geometry + churn membership + guard + async ledgers.  Every slot
        knows what hardware wrote it (validate_geometry gates the
        resume) and who was a member when it was cut; the host ledgers
        are state the same way — losing them would readmit an offender
        early or re-dispatch clients whose updates are in flight."""
        from federated_pytorch_test_tpu.utils.checkpoint import (
            mesh_geometry_meta,
        )

        meta = {}
        meta.update(mesh_geometry_meta(
            devices=self.D, processes=jax.process_count(), K=self.cfg.K,
            members=self._members if self._churn_live else None))
        if self._churn_live:
            meta["members_joined"] = np.asarray(self._members_joined,
                                                np.int64)
            meta["members_left"] = np.asarray(self._members_left, np.int64)
        if self.cfg.update_guard:
            # guard state is host state: pending quarantine sentences and
            # the calibrated norm scale must survive a kill, or a resumed
            # run would readmit an offender early / drop the bound
            meta["quarantine"] = np.asarray(self._quarantine, np.int64)
            meta["guard_scale"] = np.asarray(self._guard_scale, np.float64)
        if self.cfg.async_rounds:
            # the staleness ledger is host state the same way: losing it
            # would re-dispatch clients whose updates are in flight and
            # deliver nothing they promised
            meta["async_arrival"] = np.asarray(self._async_arrival, np.int64)
            meta["async_birth"] = np.asarray(self._async_birth, np.int64)
            meta["async_rejected"] = np.asarray(self._async_rejected,
                                                np.int64)
        if self._pop_active:
            # registry ledgers ride the same meta (pop_* keys): scatter
            # the live cohort's slot rows back first so the registry is
            # self-consistent at the cut, and record whose rows the
            # state tree's [K] stacks belong to (pop_cohort)
            if self._cohort is not None:
                self._registry.scatter_ledgers(
                    self._cohort, quarantine=self._quarantine,
                    members=self._members, arrival=self._async_arrival,
                    birth=self._async_birth)
            meta.update(self._registry.meta(self._cohort))
        return meta

    def _restore_ledger_meta(self, meta) -> None:
        """Restore the kernel ledgers from checkpoint meta, with clean
        fallbacks for slots that predate each ledger family."""
        if self.cfg.update_guard:
            if "quarantine" in meta:
                self._quarantine = np.asarray(meta["quarantine"], np.int64)
                self._guard_scale = float(meta["guard_scale"])
            else:           # checkpoint predates the guards: start clean
                self._quarantine = np.zeros(self.cfg.K, np.int64)
                self._guard_scale = float("inf")
        if self.cfg.async_rounds:
            if "async_arrival" in meta:
                self._async_arrival = np.asarray(meta["async_arrival"],
                                                 np.int64)
                self._async_birth = np.asarray(meta["async_birth"],
                                               np.int64)
                self._async_rejected = int(meta["async_rejected"])
            else:           # checkpoint predates async mode: empty buffer
                self._async_arrival = np.full(self.cfg.K, -1, np.int64)
                self._async_birth = np.zeros(self.cfg.K, np.int64)
                self._async_rejected = 0
        if self._churn_live:
            if "members" in meta:
                self._members = np.asarray(meta["members"], bool)
                self._members_joined = int(meta.get("members_joined", 0))
                self._members_left = int(meta.get("members_left", 0))
            else:           # checkpoint predates churn: full roster
                self._members = np.ones(self.cfg.K, bool)
                self._members_joined = 0
                self._members_left = 0
            self._rejoined_mask = np.zeros(self.cfg.K, bool)
        if self._pop_active:
            # registry restore AFTER the slot ledgers: the slot arrays
            # above are the checkpointed cohort's rows, and pop_cohort
            # says which rids they (and the state tree's comp rows)
            # belong to.  A slot that predates population mode returns
            # None — clean registry, first round draws cohort 0 fresh.
            self._cohort = self._registry.restore(meta)
            self._pop_comp_prev = self._cohort
            self._pop_slot_mask = None

    def _reset_block_ledgers(self) -> None:
        """Block-boundary ledger reset: a fresh block means a fresh
        delta scale (the guard norm bound recalibrates — no bound until
        one clean round) and voids every in-flight async update (the
        flat block vector they promise no longer exists).  The
        cumulative rejection counter survives — it is run-scoped."""
        self._guard_scale = float("inf")
        self._async_arrival = np.full(self.cfg.K, -1, np.int64)
        self._async_birth = np.zeros(self.cfg.K, np.int64)
        if self._registry is not None:
            # the registry's async ledger + per-block EF rows void with
            # the block for the same reason the slot arrays do
            self._registry.reset_block()
            self._pop_comp_prev = None

    # ------------------------------------------------------------------
    # observability: recorder, client ledger, spans, health, control
    # ------------------------------------------------------------------
    @staticmethod
    def _obs_sync(obs, *values):
        """Close out async dispatch at an obs phase-timing boundary
        (graftcheck JG104): when obs is recording, the stage/train/comm
        segment timings must measure execution, not dispatch — see
        PARITY.md for the timing-semantics change.  No-op with obs off,
        preserving the single-host-sync-per-round fast path."""
        if obs.enabled:
            jax.block_until_ready([v for v in values if v is not None])

    def _open_obs(self, *, resumed: bool, rounds_prior: int):
        """Open a RunRecorder for this run (obs/): emits the run-header
        event (config snapshot, mesh shape, jax/backend versions, git
        rev) and is fed one schema-validated record per comm round.

        Sinks come from ``cfg.obs_sinks``/``cfg.obs_dir`` ("auto"+None
        resolves to no sinks, so bare engine-API runs stay file-free and
        the recorder is a no-op — emission is host-side at round
        boundaries either way, never inside jitted code).
        """
        import dataclasses as _dc

        from federated_pytorch_test_tpu.obs import make_recorder

        cfg = self.cfg
        run_name = (self.obs_run_name
                    or f"{self.obs_engine}_{self.algo.name}")
        rec = make_recorder(
            getattr(cfg, "obs_sinks", "auto"), getattr(cfg, "obs_dir", None),
            run_name=run_name, engine=self.obs_engine,
            algorithm=self.algo.name)
        rec.open(config=_dc.asdict(cfg), mesh_shape=dict(self.mesh.shape),
                 resumed=resumed, rounds_prior=rounds_prior)
        # live run-health watchdog (obs/health.py): attached even when no
        # sink is configured — it only reads the per-round values the
        # engine already fetched at the round boundary, so "off" vs
        # "warn" is bit-identical training math either way
        from federated_pytorch_test_tpu.obs.health import monitor_from_config
        monitor_from_config(cfg, recorder=rec)
        # closed-loop controller (control/policy.py): attached AFTER the
        # monitor so the recorder can feed it round N before round N's
        # alerts (file order — the replay contract).  None when
        # cfg.control == "off": nothing attached, the stream and the
        # training math are bit-identical to the uncontrolled path.
        from federated_pytorch_test_tpu.control.policy import (
            controller_from_config)
        controller_from_config(cfg, recorder=rec)
        self.obs_recorder = rec
        return rec

    # -- the host timeline outside the round windows (schema v15) -------
    # A round record spans [t_round, t_round + round_seconds].  What the
    # host does outside of that is stamped too, so that the whole run is
    # under a span of the program's own: `block_switch` (with the parts
    # the engine stamped inside it) ahead of a block visit's first round
    # and `round_tail` behind every round.  The marks are perf_counter
    # stamps the engine took anyway; they are kept only while the
    # recorder is on, and a mark is EMITTED with the next round's spans
    # (or at the run's end), because emitting is itself part of a tail.
    def _mark_block_switch(self, stamps, t_end: float,
                           round_index: int) -> None:
        """``stamps``: the switch's start, then the end of each part in
        ``BLOCK_SWITCH_PARTS`` order; ``t_end``: the first round's
        ``t_round``."""
        sid = uuid.uuid4().hex[:12]
        self._outer_marks.append(
            ("block_switch", stamps[0], t_end, round_index, sid, None))
        for name, t0, t1 in zip(BLOCK_SWITCH_PARTS, stamps, stamps[1:]):
            self._outer_marks.append(
                (name, t0, t1, round_index, uuid.uuid4().hex[:12], sid))

    def _open_round_tail(self, round_index: int, t0: float) -> None:
        """The round's window just closed at ``t0``.  The id the tail's
        span will carry is fixed now, for the ``ckpt`` child that is
        emitted before the tail ends."""
        self._tail_open = (round_index, t0, uuid.uuid4().hex[:12])

    def _close_round_tail(self) -> None:
        """The round's bookkeeping is over (``on_round`` returned, or the
        run is being aborted from inside it).  No-op without an open
        tail."""
        if self._tail_open is not None:
            round_index, t0, sid = self._tail_open
            self._tail_open = None
            self._outer_marks.append(
                ("round_tail", t0, time.perf_counter(), round_index, sid,
                 None))

    def _flush_outer_spans(self, obs) -> None:
        """Emit the pending marks as spans under the RUN span (they lie
        outside every round window, like ``ckpt`` always did)."""
        marks, self._outer_marks = self._outer_marks, []
        for name, t0, t1, round_index, sid, parent in marks:
            obs.span(name, t0, t1, cat="phase", round_index=round_index,
                     span_id=sid, parent_span=parent)

    def _emit_client_record(self, obs, round_index: int, N: int,
                            loss_host, cl_nrm, cl_dist) -> None:
        """Fold this round's per-client host arrays — the activity/guard
        stash (``self._client_round``) plus the probe norms and [K] loss
        vector the round sync already fetched — into one ``client``
        record (schema v10, obs/clients.py).  Advisory telemetry: every
        value here was computed anyway; nothing reads it back."""
        from federated_pytorch_test_tpu.obs.clients import (
            client_round_fields,
        )
        cr = self._client_round
        fields = client_round_fields(
            round_index, self.cfg.K,
            update_norm=cl_nrm, dist_z=cl_dist, loss=loss_host,
            weight=cr.get("weight"), active=cr.get("active"),
            guard_ok=cr.get("guard_ok"), quarantine=cr.get("quarantine"),
            dropped=cr.get("dropped"), straggled=cr.get("straggled"),
            corrupted=cr.get("corrupted"), staleness=cr.get("staleness"),
            admitted=cr.get("admitted"), members=cr.get("members"),
            registry_ids=self._cohort if self._pop_active else None,
            payload_bytes=self.round_bytes_on_wire(N, 1))
        obs.client_event(fields)
        self._client_round = {}

    def _emit_round_obs(self, obs, rec, *, round_index, t_round,
                        images=None, extra_fields=None, N=0,
                        loss_host=None, cl_nrm=None, cl_dist=None,
                        phase_marks=(), t_ckpt=None, ledger_events=(),
                        checkpoint_path=None, state=None, blockvars=None,
                        nxt=None, history=None, log=print):
        """One comm round's observability fan-out, shared by every
        engine: the schema-validated round record, the client-grain
        flight-recorder line, the phase/ckpt/compile spans, then the
        health watchdog and control-plane checks (in that order — a
        fatal health trip owns the exit, the supervisor owns recovery).

        ``phase_marks`` is ``[(name, cat, t0, t1), ...]`` span bounds
        the engine collected from timestamps it already took; the ckpt
        span (after ``round_seconds`` is measured) hangs off the round's
        open ``round_tail`` span (the RUN span where an engine stamps no
        tail) and late-drained compile events off the
        RUN span, to keep nesting laminar (obs/trace.py).  The spans
        stamped outside the round windows since the last emission (the
        previous round's tail, this round's block switch) ride along."""
        from federated_pytorch_test_tpu.obs import device_memory_stats

        if not (obs.enabled or obs.health is not None
                or obs.control is not None):
            return
        extra = dict(rec, round_index=round_index, t_start=t_round,
                     **device_memory_stats())
        if images is not None:
            extra["images"] = images
        if extra_fields:
            extra.update(extra_fields)
        rrec = obs.round(extra)
        if self._client_probe:
            # the round's flight-recorder line: one additive `client`
            # record right behind the round record (schema v10)
            self._emit_client_record(obs, round_index, N, loss_host,
                                     cl_nrm, cl_dist)
        if self.campaign is not None:
            # the campaign window transition, if any, rides right behind
            # the round record too (schema v12)
            self._emit_campaign_record(obs, round_index)
        if self._serve_sched is not None and state is not None:
            # the serving tick rides the round boundary: hot-swap at the
            # schedule's cadence, answer this round's seeded traffic,
            # emit the additive `serve` record (schema v13)
            self._serve_tick(obs, round_index, state, log=log)
        if obs.enabled:
            rspan = (rrec or {}).get("span_id")
            self._flush_outer_spans(obs)
            for nm, cat, s0, s1 in phase_marks:
                obs.span(nm, s0, s1, cat=cat, round_index=round_index,
                         parent_span=rspan)
            if t_ckpt is not None:
                # the mid-run save runs AFTER round_seconds is measured,
                # so its span lies in the round's tail, not in the round
                obs.span("ckpt", t_ckpt,
                         t_ckpt + rec["ckpt_write_seconds"],
                         cat="ckpt", round_index=round_index,
                         parent_span=(self._tail_open[2]
                                      if self._tail_open else None))
            t_hi = t_round + rec["round_seconds"] + 1e-9
            for cev in ledger_events:
                # in-window compiles nest inside the round span; late-
                # drained ones (eval compiles from a prior round) hang
                # off the RUN span to keep nesting laminar
                in_rnd = (rspan is not None
                          and cev.t_start >= t_round - 1e-9
                          and cev.t_end <= t_hi)
                obs.compile_event(
                    cev.record(round_index=round_index),
                    parent_span=rspan if in_rnd else None)
        if obs.health is not None and obs.health.tripped is not None:
            self._health_abort(obs, checkpoint_path, state, blockvars,
                               nxt, history, log)
        if obs.control is not None:
            # round-scope interventions apply AFTER the health check: a
            # fatal trip owns the exit, and the supervisor owns the
            # recovery
            self._apply_round_control(obs, checkpoint_path, log)

    def _health_abort(self, obs, checkpoint_path, state, blockvars, nxt,
                      history, log=print):
        """A watchdog rule tripped with a fatal ``--health-action``.

        ``checkpoint-abort``: the tripping round already went through
        ``_save_midrun`` when mid-run checkpointing is on; otherwise a
        one-off save lands at ``<checkpoint_dir>/<run_name>_health_abort``.
        Either way the async writer is drained and the newest slot is
        checksum-verified BEFORE raising, so the run dies with a
        proven-good checkpoint on disk.  Always ends in
        :class:`~..obs.health.RunHealthAbort`; ``run()``'s handler then
        closes the obs stream with status="aborted".
        """
        from federated_pytorch_test_tpu.obs.health import RunHealthAbort

        alert = obs.health.tripped
        log(f"health: rule {alert.get('rule')!r} tripped on round "
            f"{alert.get('round_index')} (action={obs.health.action})")
        if obs.health.action == "checkpoint-abort":
            from federated_pytorch_test_tpu.utils.checkpoint import (
                finalize_checkpoint,
            )

            path = checkpoint_path
            if path is None:
                run_name = (self.obs_run_name
                            or f"{self.obs_engine}_{self.algo.name}")
                path = os.path.join(self.cfg.checkpoint_dir,
                                    f"{run_name}_health_abort")
                self._save_midrun(path, state, blockvars, nxt, history)
            self._flush_ckpt_writer()
            from federated_pytorch_test_tpu.utils.checkpoint import (
                NoUsableCheckpointError,
            )
            try:
                slot = finalize_checkpoint(path)
            except NoUsableCheckpointError as e:
                # no slot ever landed (e.g. the async writer's save
                # failed): degrade to a plain abort — the health alert
                # must surface, not a secondary checkpoint error
                log(f"WARNING: health: no usable checkpoint to finalize "
                    f"({e}); aborting without one")
            else:
                log(f"health: final checkpoint verified at {slot}")
        raise RunHealthAbort(alert)

    def _apply_round_control(self, obs, checkpoint_path, log=print):
        """Apply act-mode round-scope decisions at the round boundary.

        ``max_staleness`` is read from ``self.cfg`` on the host every
        round (``_round_activity_async``), so swapping the config
        dataclass applies it live — no recompile, no device traffic.
        A ``checkpoint_restart`` decision flushes + verifies the newest
        checkpoint slot and raises :class:`ControlRestart` for the
        restart supervisor.
        """
        import dataclasses as _dc

        ctl = obs.control
        for d in ctl.take_round():
            if d.param == "max_staleness":
                with self._cfg_swap_lock:
                    old = self.cfg.max_staleness
                    self.cfg = _dc.replace(self.cfg,
                                           max_staleness=int(d.to_value))
                log(f"control: {d.intervention} max_staleness "
                    f"{old} -> {self.cfg.max_staleness} ({d.reason})")
            elif d.param == "cohort_frac":
                # cohort-size rung: host-side knob read at the next
                # cohort rotation (_population_round_begin) — no
                # recompile, the compiled round stays [K]-shaped and
                # inactive slots are masked out
                if not self._pop_active:
                    log("control: skip cohort_frac (population mode "
                        "is off for this run)")
                    continue
                old_f = self._cohort_frac
                self._cohort_frac = float(d.to_value)
                log(f"control: {d.intervention} cohort_frac "
                    f"{old_f} -> {self._cohort_frac} ({d.reason})")
            elif d.param == "serve_swap":
                # serve-drift rung: arm a forced refresh — the NEXT
                # round's serve tick republishes the current consensus
                # WITHOUT bumping weights_version, so the pure swap
                # sequence control.replay re-derives is untouched
                if self._serve_sched is None:
                    log("control: skip serve_swap (serving is off for "
                        "this run)")
                    continue
                self._serve_forced = True
                log(f"control: {d.intervention} armed a forced serving "
                    f"refresh ({d.reason})")
        d = ctl.take_restart()
        if d is not None:
            from federated_pytorch_test_tpu.control.policy import (
                ControlRestart,
            )
            from federated_pytorch_test_tpu.utils.checkpoint import (
                finalize_checkpoint,
            )
            self._flush_ckpt_writer()
            slot = finalize_checkpoint(checkpoint_path)
            log(f"control: checkpoint-then-restart from verified {slot} "
                f"({d.reason})")
            raise ControlRestart(
                d.fields(source="policy", mode="act", applied=True))
