"""Mid-run checkpoint/resume tests (SURVEY.md section 5).

The contract: kill a run after any communication round, resume from the
checkpoint, and the continued history/params must match an uninterrupted
run exactly (same staging PRNG, same optimizer state, same ADMM state).
"""

import numpy as np
import pytest

from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
from federated_pytorch_test_tpu.models.simple import Net
from federated_pytorch_test_tpu.train import (
    AdmmConsensus,
    BlockwiseFederatedTrainer,
    FederatedConfig,
)

K = 4


class Killed(Exception):
    pass


def small_cfg(**kw):
    base = dict(K=K, Nloop=1, Nepoch=1, Nadmm=3, default_batch=8,
                check_results=False, admm_rho0=0.1, seed=5)
    base.update(kw)
    return FederatedConfig(**base)


@pytest.fixture(scope="module")
def data():
    return FederatedCifar10(K=K, batch=8, limit_per_client=16, limit_test=8)


def run_trainer(cfg, data, L=1, **run_kw):
    t = BlockwiseFederatedTrainer(Net(), cfg, data, AdmmConsensus())
    t.L = L
    run_kw.setdefault("log", lambda m: None)
    return t.run(**run_kw)


def strip(rec):
    # wall-clock fields legitimately differ between runs (a resumed
    # process re-compiles at its first continued round), and a resumed
    # segment's first round is a block visit's first round: it stamps a
    # switch
    return {k: v for k, v in rec.items()
            if isinstance(v, (int, float)) and not k.endswith("_seconds")
            and not k.startswith("dispatch_")
            and k != "block_switch_h2d_bytes"}


class TestMidrunResume:
    # both checkpoint write paths honor the kill/resume contract: the
    # async writer's abort-path drain makes the last submitted round
    # durable before the trainer dies, exactly like the sync save
    @pytest.mark.parametrize("async_ckpt", [False, True],
                             ids=["sync", "async"])
    def test_killed_run_resumes_to_identical_history(self, data, tmp_path,
                                                     async_ckpt):
        cfg = small_cfg(async_checkpoint=async_ckpt)
        ck = str(tmp_path / "ck")

        _, hist_full = run_trainer(cfg, data)

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, checkpoint_path=ck, on_round=bomb)

        state_r, hist_r = run_trainer(cfg, data, checkpoint_path=ck,
                                      resume=True)
        assert len(hist_r) == len(hist_full)
        # restored prefix + continued rounds must match the uninterrupted
        # run: same shuffle PRNG state, optimizer state, and z/y/rho
        for a, b in zip(hist_r, hist_full):
            sa, sb = strip(a), strip(b)
            assert sa.keys() == sb.keys()
            for k in sa:
                np.testing.assert_allclose(sa[k], sb[k], rtol=1e-5,
                                           err_msg=f"history field {k}")

    def test_params_match_uninterrupted(self, data, tmp_path):
        cfg = small_cfg(Nadmm=2)
        ck = str(tmp_path / "ck")
        state_full, _ = run_trainer(cfg, data)

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, checkpoint_path=ck, on_round=bomb)
        state_r, _ = run_trainer(cfg, data, checkpoint_path=ck, resume=True)

        ref = jax_to_np(state_full.params)
        res = jax_to_np(state_r.params)
        for (pa, a), (pb, b) in zip(ref, res):
            assert pa == pb
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                       err_msg=str(pa))

    def test_block_boundary_resume(self, data, tmp_path):
        # kill exactly at a block rollover: the checkpoint then carries no
        # block vars (fresh-init path on resume) — both blocks must run
        cfg = small_cfg(Nadmm=1)
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(cfg, data, L=2)

        seen = []

        def bomb(state, rec):
            seen.append(rec["block"])
            if rec["block"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, L=2, checkpoint_path=ck, on_round=bomb)
        _, hist_r = run_trainer(cfg, data, L=2, checkpoint_path=ck,
                                resume=True)
        assert [h["block"] for h in hist_r] == [h["block"] for h in hist_full]
        for a, b in zip(hist_r, hist_full):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)

    @pytest.mark.parametrize("comp_kw", [
        pytest.param(dict(compress="q8"), id="q8"),
        pytest.param(dict(compress="topk", topk_frac=0.1,
                          error_feedback=True), id="topk_ef"),
        pytest.param(dict(compress="q8", fused_collective=True),
                     marks=pytest.mark.fusedcomm, id="q8_fused"),
        pytest.param(dict(compress="q8", overlap_staging=True),
                     marks=pytest.mark.fusedcomm, id="q8_overlap"),
    ])
    def test_compressed_state_resumes_identically(self, data, tmp_path,
                                                  comp_kw):
        # the per-client compressor state (PRNG key / EF residual) rides
        # in the midrun checkpoint: a resumed compressed run must replay
        # the uninterrupted trajectory exactly — including through the
        # packed-collective comm path and the prestage-overlap cache
        # (both are keyed on round counters, so resume re-derives them)
        cfg = small_cfg(**comp_kw)
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(cfg, data)

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, checkpoint_path=ck, on_round=bomb)
        state_r, hist_r = run_trainer(cfg, data, checkpoint_path=ck,
                                      resume=True)
        assert state_r.comp is not None
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
            assert a["bytes_on_wire"] == b["bytes_on_wire"]

    def test_pre_compression_checkpoint_resumes_with_fresh_comp(
            self, data, tmp_path):
        # a checkpoint written by a DENSE run carries no comp_state_leaves;
        # resuming it under a compressed config must fall back to fresh
        # per-client state instead of failing (engine _restore_midrun)
        ck = str(tmp_path / "ck")

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(small_cfg(), data, checkpoint_path=ck, on_round=bomb)
        state_r, hist_r = run_trainer(small_cfg(compress="q8"), data,
                                      checkpoint_path=ck, resume=True)
        assert state_r.comp is not None
        assert len(hist_r) == 3                 # Nadmm=3 rounds completed
        # the continued rounds report the compressed wire size
        comp_bytes = hist_r[-1]["bytes_on_wire"]
        assert 0 < comp_bytes < K * 4 * hist_r[-1]["N"]

    def test_completed_run_resume_is_noop(self, data, tmp_path):
        cfg = small_cfg(Nadmm=1)
        ck = str(tmp_path / "ck")
        _, hist = run_trainer(cfg, data, checkpoint_path=ck)
        state2, hist2 = run_trainer(cfg, data, checkpoint_path=ck,
                                    resume=True)
        # nothing left to do: restored history returned unchanged
        assert len(hist2) == len(hist)


def jax_to_np(tree):
    import jax

    # jax.tree_util spelling: jax.tree.flatten_with_path only exists in
    # newer jax releases than the pinned 0.4.x
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), np.asarray(v)) for p, v in flat]


class TestSlotSwapCrashWindows:
    """Slot-level crash-window coverage for save_checkpoint_swapped.

    The window: a kill AFTER save to ``path.next`` finalized but BEFORE the
    swap renamed it into ``path`` leaves the NEWER checkpoint in ``.next``
    and the round-stale one in ``path``; the probe must prefer ``.next``
    (when present it is always the newest by protocol) and the next swap
    must not rmtree it.
    """

    @staticmethod
    def _save(path, round_):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            save_checkpoint,
        )

        save_checkpoint(path, {"x": np.float32(round_)},
                        {"round": round_})

    @staticmethod
    def _round_of(path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            load_checkpoint,
        )

        return load_checkpoint(path)[1]["round"]

    def test_newest_slot_prefers_next(self, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import newest_slot

        ck = str(tmp_path / "ck")
        self._save(ck, 1)                # stale primary (round 1)
        self._save(ck + ".next", 2)      # crash-stranded newer save
        assert newest_slot(ck) == ck + ".next"
        assert self._round_of(newest_slot(ck)) == 2

    def test_swap_after_crash_keeps_newer(self, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            newest_slot,
            save_checkpoint_swapped,
        )

        ck = str(tmp_path / "ck")
        self._save(ck, 1)
        self._save(ck + ".next", 2)
        # the resumed run restores round 2 and checkpoints round 3: the
        # swap must promote .next (round 2) over the stale primary, never
        # leaving the newest data in a slot its own rmtree then deletes
        save_checkpoint_swapped(ck, {"x": np.float32(3)}, {"round": 3})
        assert newest_slot(ck) == ck
        assert self._round_of(ck) == 3

    def test_checksum_sidecar_written_and_verifies(self, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            CHECKSUM_FILE,
            verify_checkpoint,
        )

        ck = str(tmp_path / "ck")
        self._save(ck, 1)
        assert (tmp_path / "ck" / CHECKSUM_FILE).exists()
        assert verify_checkpoint(ck) is True

    def test_tampered_checkpoint_fails_verification(self, tmp_path):
        import os

        from federated_pytorch_test_tpu.utils.checkpoint import (
            CHECKSUM_FILE,
            CheckpointCorruptError,
            verify_checkpoint,
        )

        ck = str(tmp_path / "ck")
        self._save(ck, 1)
        victim = next(
            os.path.join(r, f) for r, _, fs in os.walk(ck)
            for f in fs if f != CHECKSUM_FILE)
        with open(victim, "r+b") as fh:      # flip one byte in place
            b = fh.read(1)
            fh.seek(0)
            fh.write(bytes([b[0] ^ 0xFF]))
        with pytest.raises(CheckpointCorruptError):
            verify_checkpoint(ck)

    def test_swap_sweeps_stranded_orbax_tmp_dirs(self, tmp_path):
        import os
        import time

        from federated_pytorch_test_tpu.utils.checkpoint import (
            save_checkpoint_swapped,
        )

        ck = str(tmp_path / "ck")
        stranded = tmp_path / "ck.next.orbax-checkpoint-tmp-12345"
        stranded.mkdir()
        (stranded / "partial").write_bytes(b"x")
        fresh = tmp_path / "ck.next.orbax-checkpoint-tmp-67890"
        fresh.mkdir()
        # stranded = provably stale (a crashed earlier run); fresh = could
        # be a skewed peer's in-flight save on a shared fs — must survive
        old = time.time() - 7200
        os.utime(stranded, (old, old))
        save_checkpoint_swapped(ck, {"x": np.float32(1)}, {"round": 1})
        assert not stranded.exists()
        assert fresh.exists()
        assert self._round_of(ck) == 1


class TestCorruptSlotFallback:
    """Atomic-checkpoint satellite: a bit-rotted or truncated slot must not
    kill the resume — the engine walks newest-to-oldest, warns, and falls
    back; only when EVERY slot is bad does it raise CheckpointCorruptError.
    """

    @staticmethod
    def _corrupt_slot(slot):
        import os

        from federated_pytorch_test_tpu.utils.checkpoint import (
            CHECKSUM_FILE,
        )

        victim = next(
            os.path.join(r, f) for r, _, fs in os.walk(slot)
            for f in fs if f != CHECKSUM_FILE)
        with open(victim, "r+b") as fh:
            b = fh.read(1)
            fh.seek(0)
            fh.write(bytes([b[0] ^ 0xFF]))

    def _bombed_run_with_slots(self, data, ck, **cfg_kw):
        """Kill after round 1 so BOTH ck (round 1) and ck.old (round 0)
        checkpoint slots exist when the resume probes them."""
        def bomb(state, rec):
            if rec["nadmm"] == 1:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(small_cfg(**cfg_kw), data, checkpoint_path=ck,
                        on_round=bomb)

    # the async writer must preserve the slot protocol (rotation order,
    # sha256 sidecars) byte-for-byte — the corrupt-slot walk is the proof
    @pytest.mark.parametrize("async_ckpt", [False, True],
                             ids=["sync", "async"])
    def test_corrupt_primary_falls_back_to_old_slot(self, data, tmp_path,
                                                    async_ckpt):
        import os

        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(small_cfg(), data)
        self._bombed_run_with_slots(data, ck, async_checkpoint=async_ckpt)
        assert os.path.isdir(ck + ".old")
        self._corrupt_slot(ck)

        msgs = []
        _, hist_r = run_trainer(small_cfg(), data, checkpoint_path=ck,
                                resume=True, log=msgs.append)
        assert any("unusable" in m and "falling back" in m for m in msgs)
        # the stale slot is one round behind: the resumed run replays that
        # round and must still land on the uninterrupted history exactly
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
            np.testing.assert_allclose(a["dual_residual"],
                                       b["dual_residual"], rtol=1e-5)

    def test_all_slots_corrupt_raises(self, data, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            CheckpointCorruptError,
            checkpoint_slots,
        )

        ck = str(tmp_path / "ck")
        self._bombed_run_with_slots(data, ck)
        slots = checkpoint_slots(ck)
        assert len(slots) >= 2
        for slot in slots:
            self._corrupt_slot(slot)
        with pytest.raises(CheckpointCorruptError, match="no valid"):
            run_trainer(small_cfg(), data, checkpoint_path=ck,
                        resume=True, log=lambda m: None)


class TestAsyncRunResume:
    """--async-rounds kill/resume (ISSUE 6): the staleness ledger
    (arrival round, birth round, cumulative rejections) rides in the
    checkpoint meta and the frozen per-client params ARE the in-flight
    buffer, so a resumed async run must replay the uninterrupted
    trajectory exactly — through both checkpoint writers."""

    ASYNC_CFG = dict(Nadmm=4, async_rounds=True, max_staleness=2,
                     fault_spec="delay=0.5,delay_max=2,seed=9")
    LEDGER_FIELDS = ("async_arrived", "admission_rejected", "buffer_depth",
                     "n_active")

    @pytest.mark.asyncfl
    @pytest.mark.parametrize("async_ckpt", [False, True],
                             ids=["sync", "async"])
    def test_async_run_resumes_identically(self, data, tmp_path,
                                           async_ckpt):
        cfg = small_cfg(async_checkpoint=async_ckpt, **self.ASYNC_CFG)
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(cfg, data)
        # the kill point must leave updates in flight, or the ledger
        # restore proves nothing
        assert hist_full[1]["buffer_depth"] > 0

        def bomb(state, rec):
            if rec["nadmm"] == 1:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, checkpoint_path=ck, on_round=bomb)
        _, hist_r = run_trainer(cfg, data, checkpoint_path=ck, resume=True)
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            sa, sb = strip(a), strip(b)
            assert sa.keys() == sb.keys()
            # the ledger-derived counters are bit-identical by contract
            for k in self.LEDGER_FIELDS:
                assert sa[k] == sb[k], k
            assert a["staleness_hist"] == b["staleness_hist"]
            for k in sa:
                np.testing.assert_allclose(sa[k], sb[k], rtol=1e-5,
                                           err_msg=f"history field {k}")

    @pytest.mark.asyncfl
    def test_async_block_boundary_resume(self, data, tmp_path):
        # a block rollover voids the in-flight buffer (block variables
        # change identity); a kill exactly there must resume onto the
        # fresh-ledger path and still match the uninterrupted run
        cfg = small_cfg(Nadmm=2, async_rounds=True, max_staleness=2,
                        fault_spec="delay=0.5,delay_max=2,seed=9")
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(cfg, data, L=2)

        def bomb(state, rec):
            if rec["block"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, L=2, checkpoint_path=ck, on_round=bomb)
        _, hist_r = run_trainer(cfg, data, L=2, checkpoint_path=ck,
                                resume=True)
        assert [h["block"] for h in hist_r] == \
            [h["block"] for h in hist_full]
        for a, b in zip(hist_r, hist_full):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
            assert a["buffer_depth"] == b["buffer_depth"]


class TestElasticResume:
    """Mesh-reshaping resume (elastic-federation tentpole): a checkpoint
    written on a D-device mesh must restore onto a D'-device mesh when
    ``elastic_resume`` is set — the K client rows restage onto whatever
    mesh the resuming process built (PARITY.md: bitwise when D' == D,
    allclose trajectory + exact history shape when D' != D) — and must
    fail with the typed ``CheckpointGeometryError`` when it is not."""

    @pytest.fixture(scope="class")
    def data8(self):
        return FederatedCifar10(K=8, batch=8, limit_per_client=16,
                                limit_test=8)

    @staticmethod
    def e_cfg(d, **kw):
        base = dict(K=8, Nloop=1, Nepoch=1, Nadmm=3, default_batch=8,
                    check_results=False, admm_rho0=0.1, seed=5,
                    num_devices=d)
        base.update(kw)
        return FederatedConfig(**base)

    @pytest.mark.parametrize("d_from,d_to", [
        pytest.param(8, 8, id="8to8"),
        pytest.param(8, 4, id="8to4"),
        pytest.param(4, 8, id="4to8"),
    ])
    def test_reshape_resume_matches_uninterrupted(self, data8, tmp_path,
                                                  d_from, d_to):
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(self.e_cfg(d_from), data8)

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(self.e_cfg(d_from), data8, checkpoint_path=ck,
                        on_round=bomb)
        _, hist_r = run_trainer(self.e_cfg(d_to, elastic_resume=True),
                                data8, checkpoint_path=ck, resume=True)
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            sa, sb = strip(a), strip(b)
            assert sa.keys() == sb.keys()
            for k in sa:
                if d_from == d_to:
                    # same geometry: the elastic flag must not perturb
                    # the bitwise kill/resume contract
                    np.testing.assert_array_equal(
                        sa[k], sb[k], err_msg=f"history field {k}")
                else:
                    # reshaped mesh: cross-device reduction order moves,
                    # so the contract is allclose, not bitwise
                    np.testing.assert_allclose(
                        sa[k], sb[k], rtol=1e-4, atol=1e-6,
                        err_msg=f"history field {k}")

    def test_geometry_mismatch_without_flag_raises(self, data8, tmp_path):
        from federated_pytorch_test_tpu.utils.checkpoint import (
            CheckpointGeometryError,
        )

        ck = str(tmp_path / "ck")

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(self.e_cfg(8), data8, checkpoint_path=ck,
                        on_round=bomb)
        with pytest.raises(CheckpointGeometryError, match="elastic"):
            run_trainer(self.e_cfg(4), data8, checkpoint_path=ck,
                        resume=True)
        # the error is actionable, not fatal to the data: the same resume
        # succeeds once the operator opts in
        _, hist_r = run_trainer(self.e_cfg(4, elastic_resume=True), data8,
                                checkpoint_path=ck, resume=True)
        assert len(hist_r) == 3

    def test_k_change_rejected_even_with_flag(self, data, tmp_path):
        # K is the federation's identity — elastic_resume covers mesh
        # geometry only, never the client axis
        from federated_pytorch_test_tpu.utils.checkpoint import (
            CheckpointGeometryError,
            load_checkpoint,
            validate_geometry,
        )

        ck = str(tmp_path / "ck")

        def bomb(state, rec):
            if rec["nadmm"] == 0:
                raise Killed

        with pytest.raises(Killed):
            run_trainer(small_cfg(), data, checkpoint_path=ck,
                        on_round=bomb)
        _, meta = load_checkpoint(ck)
        with pytest.raises(CheckpointGeometryError, match="K"):
            validate_geometry(meta, devices=8, processes=1, K=8,
                              elastic=True)


class TestChurnResume:
    """Client churn (join=/leave= fault family): the membership ledger is
    a pure function of (seed, round coords), so the same seed must yield
    the same ledger on a fresh run AND across a mid-run kill/resume —
    the live roster rides in the checkpoint meta."""

    CHURN_CFG = dict(Nadmm=4, fault_spec="join=0.4,leave=0.4,seed=11")
    LEDGER_FIELDS = ("members_active", "joined", "left")

    def test_same_seed_same_ledger(self, data):
        cfg = small_cfg(**self.CHURN_CFG)
        _, h1 = run_trainer(cfg, data)
        _, h2 = run_trainer(cfg, data)
        ledger = [tuple(h[k] for k in self.LEDGER_FIELDS) for h in h1]
        assert ledger == \
            [tuple(h[k] for k in self.LEDGER_FIELDS) for h in h2]
        # the schedule must actually churn for this suite to mean
        # anything (seed=11: roster dips to 2 of 4 members)
        assert sum(h["joined"] + h["left"] for h in h1) > 0
        assert min(h["members_active"] for h in h1) < K

    def test_churned_run_resumes_identically(self, data, tmp_path):
        cfg = small_cfg(**self.CHURN_CFG)
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(cfg, data)

        def bomb(state, rec):
            if rec["nadmm"] == 1:    # mid-churn: the roster must survive
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, checkpoint_path=ck, on_round=bomb)
        _, hist_r = run_trainer(cfg, data, checkpoint_path=ck, resume=True)
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            sa, sb = strip(a), strip(b)
            assert sa.keys() == sb.keys()
            # the ledger is bit-identical by contract
            for k in self.LEDGER_FIELDS:
                assert sa[k] == sb[k], k
            for k in sa:
                np.testing.assert_allclose(sa[k], sb[k], rtol=1e-5,
                                           err_msg=f"history field {k}")

    def test_churn_off_records_carry_no_membership_fields(self, data):
        # bit-identity satellite: a static-roster run's records must stay
        # byte-identical to schema v8 — the membership fields may only
        # appear when join=/leave= is configured
        _, hist = run_trainer(small_cfg(), data)
        for h in hist:
            assert not any(k in h for k in self.LEDGER_FIELDS)


class TestFaultyRunResume:
    """Fault schedule + guard/quarantine state across a kill/resume: the
    continued run must replay the interrupted trajectory bit-for-bit —
    the fault draws are stateless in the round coordinates and the
    quarantine ledger + guard scale ride in the checkpoint meta."""

    FAULT_CFG = dict(
        Nadmm=4,
        fault_spec="drop=0.3,corrupt=0.5,mode=nan,seed=7",
        update_guard=True, quarantine_rounds=1,
    )

    def test_faulty_guarded_run_resumes_identically(self, data, tmp_path):
        cfg = small_cfg(**self.FAULT_CFG)
        ck = str(tmp_path / "ck")
        _, hist_full = run_trainer(cfg, data)
        # the schedule must actually exercise faults + the guard for this
        # test to mean anything
        assert sum(h["fault_corrupted"] for h in hist_full) > 0
        assert sum(h["guard_trips"] for h in hist_full) > 0
        assert sum(h["quarantined"] for h in hist_full) > 0

        def bomb(state, rec):
            if rec["nadmm"] == 1:    # mid-quarantine: ledger must survive
                raise Killed

        with pytest.raises(Killed):
            run_trainer(cfg, data, checkpoint_path=ck, on_round=bomb)
        _, hist_r = run_trainer(cfg, data, checkpoint_path=ck, resume=True)
        assert len(hist_r) == len(hist_full)
        for a, b in zip(hist_r, hist_full):
            sa, sb = strip(a), strip(b)
            assert sa.keys() == sb.keys()
            for k in sa:
                np.testing.assert_allclose(sa[k], sb[k], rtol=1e-5,
                                           err_msg=f"history field {k}")
