"""Engine kind ``decoder_dense``: ``engines/decoder_tied.py``'s trainer and
window (two untimed sweeps) for a dense decoder, one without an expert
layer, whose loss has one term and whose Gated DeltaNet layers may give
``beta`` up to 2.  The model is ``MODEL_REGISTRY[config["model"]]``, the
plain reference ``benchmarks/reference/<config["model"]>.py``.

No sibling engine serves such a configuration: ``engines/decoder.py``'s
``check`` divides by the multi-token-prediction term, ``decoder_hc.py``
judges ``mhc_marginal_err`` and ``decoder_tied.py`` the router's weight,
which read 0.0 here.  What is generic is imported:
``decoder_tied.build_trainer`` and its window loop, ``lm.compare_rounds``.
Added: the blocks whose gradient the check compares are the traffic's
``check_grad_blocks`` (they need not be trained in the window); in the
forward pass, in both rounds of the check and in every round of the run
``gdn_neg_beta_share`` strictly between 0 and 1 (at 0 or 1 no transition
of the run had a negative eigenvalue, or all had); in every round on a
TPU ``gdn_scan_impl`` and ``attn_impl`` ``pallas``; and this
configuration's own comparison.

Configuration and traffic keys read: as ``engines/decoder.py``, and
``check_grad_blocks``.
"""

from __future__ import annotations

import gc
import importlib
import time
from typing import Any, Dict, List

from benchmarks.engines import decoder_tied, lm
from benchmarks.engines.decoder_hc import _SteadyWindow
from benchmarks.lib.window import Window

build_model, build_trainer = decoder_tied.build_model, \
    decoder_tied.build_trainer


def _exercised(share) -> bool:
    return 0.0 < float(share) < 1.0


class Session(decoder_tied.Session):
    def run(self, window: Window) -> None:
        """The window behind two untimed sweeps; then over all its rounds
        which implementations ran and the share of negative eigenvalues;
        the last pass's loss not above the second untimed sweep's."""
        import jax

        self._window_of(_SteadyWindow(window))
        loss = lambda recs: sum(r["loss"] for r in recs)
        second = window.warmup[len(window.warmup) // 2:]
        if not loss(window.passes[-1].records) <= loss(second):
            self.problems.append(
                f"loss of the last pass {loss(window.passes[-1].records)!r} "
                "is not below the second untimed sweep's "
                f"{loss(second)!r}")
        records = list(window.warmup) + [r for p in window.passes
                                         for r in p.records]
        shares = [r["gdn_neg_beta_share"] for r in records]
        impls = {f: sorted({r[f] for r in records})
                 for f in ("gdn_scan_impl", "attn_impl")}
        print(f"gdn_neg_beta_share: rounds {min(shares)!r} to "
              f"{max(shares)!r}; {impls}")
        if not all(_exercised(s) for s in shares):
            self.problems.append(
                f"gdn_neg_beta_share of a round is 0 or 1 ({min(shares)!r} "
                f"to {max(shares)!r}): the negative eigenvalues were not "
                "exercised")
        for field, seen in impls.items():
            if jax.default_backend() == "tpu" and seen != ["pallas"]:
                self.problems.append(f"{field} {seen} on a TPU: the run fell "
                                     "off the kernels")

    # ------------------------------------------------------------------
    def check(self) -> Dict[str, Any]:
        """Against the plain reference (``benchmarks/reference/<model>.py``
        through ``decoder_round.py``), at the cell's widths and the timed
        step's shapes:

        (c) two FedAvg rounds of ``trainer.run()`` on the schedule's last
            block, each client's shard ONE minibatch, by the share of the
            block's elements further than ``lm.MOVED_LR`` lr from the
            reference;
        (a) logits of the model on one minibatch; printed, not judged:
            the loss (``loss_rel``: why, beside the tolerances);
        (b) the gradient of that minibatch's loss with respect to each of
            the traffic's ``check_grad_blocks``.

        The reference goes sequence by sequence so that it fits beside
        the program.
        """
        import jax
        import jax.numpy as jnp

        from benchmarks.reference import decoder_round

        cell, t0 = self.cell, time.perf_counter()
        ref = importlib.import_module(
            f"benchmarks.reference.{cell.config['model']}")
        K, batch = int(cell.config["K"]), int(cell.config["batch"])
        trainer = build_trainer(cell, self.seed, K=K,
                                samples_per_client=batch,
                                blocks=self.blocks[-1:], Nloop=1, Nadmm=2)
        model, lr = trainer.model, trainer.cfg.lr
        order, ranges = model.param_order(), model.train_order_block_ids()
        paths_of = lambda b: list(order[ranges[b][0]:ranges[b][1] + 1])
        paths = paths_of(self.blocks[-1])
        # one client's copy of the common start, kept on the device
        params = jax.tree.map(lambda a: jnp.copy(a[0]), trainer.params0)
        xs, ys = trainer.data.train_shards_raw()       # [K, B, T] each
        system: List[Dict[str, Any]] = []

        def on_round(state, rec):
            system.append({
                "x": [jnp.copy(ref.get_path(state.params, p)) for p in paths],
                "loss": rec["loss"], "moe_dropped": rec["moe_dropped"],
                "gdn_neg_beta_share": rec["gdn_neg_beta_share"]})

        try:
            trainer.run(log=lambda msg: None, on_round=on_round)
        finally:
            trainer.close()
        del trainer
        gc.collect()
        t_system = time.perf_counter()

        problems, out = self.problems, {}
        # (a), (b): one minibatch, client 0's
        ids, labels = jnp.asarray(xs[0]), jnp.asarray(ys[0])
        grad_blocks = [int(b) for b in cell.traffic.get(
            "check_grad_blocks", self.blocks[:2])]
        gpaths = [p for b in grad_blocks for p in paths_of(b)]

        def system_loss(leaves, p, ids, labels):
            for path, leaf in zip(gpaths, leaves):
                p = ref.set_path(p, path, leaf)
            per_seq, aux = model.apply({"params": p}, ids, labels)
            return jnp.mean(per_seq), aux

        (loss, aux), grads = jax.jit(
            jax.value_and_grad(system_loss, has_aux=True))(
                [ref.get_path(params, p) for p in gpaths], params, ids,
                labels)
        logits = jax.jit(lambda p, ids: model.apply({"params": p}, ids)[0])(
            params, ids)
        want_loss = err2 = ref2 = 0.0
        want_grads = None
        for i in range(batch):
            l, seen, g = ref.loss_and_grad(cell.config, params, gpaths,
                                           ids[i], labels[i])
            want_loss += float(l) / batch
            err2 += float(jnp.sum((logits[i] - seen["logits"]) ** 2))
            ref2 += float(jnp.sum(seen["logits"] ** 2))
            g = [gi / batch for gi in g]
            want_grads = g if want_grads is None else [
                a + b for a, b in zip(want_grads, g)]
            del seen
        out["loss"] = float(loss)
        out["loss_rel"] = abs(float(loss) - want_loss) / abs(want_loss)
        out["logits_rel"] = (err2 / ref2) ** 0.5
        out["gdn_neg_beta_share"] = float(aux["gdn_neg_beta_share"])
        if not out["logits_rel"] <= LOGITS_RTOL:
            problems.append(f"forward: logits_rel {out['logits_rel']:.2e} > "
                            f"{LOGITS_RTOL} (loss {float(loss)!r} vs "
                            f"reference {want_loss!r})")
        if not _exercised(out["gdn_neg_beta_share"]):
            problems.append("forward: gdn_neg_beta_share "
                            f"{out['gdn_neg_beta_share']!r} is 0 or 1")
        sq = lambda leaves: float(sum(jnp.sum(a * a) for a in leaves))
        lo = 0
        for b in grad_blocks:
            n = len(paths_of(b))
            got, want = grads[lo:lo + n], want_grads[lo:lo + n]
            lo += n
            rel = (sq([a - w for a, w in zip(got, want)]) / sq(want)) ** 0.5
            out[f"grad_rel_block{b}"] = rel
            if not rel <= GRAD_RTOL:
                problems.append(f"gradient of block {b} differs from the "
                                f"reference by {rel:.2e} of its norm "
                                f"(> {GRAD_RTOL})")
        del grads, want_grads, logits
        t_forward = time.perf_counter()

        # (c): the reference's two rounds, client by client
        batches = [[[(xs[k], ys[k])] for k in range(K)] for _ in range(2)]
        expected = decoder_round.run_rounds(ref, cell.config, params, paths,
                                            lr, batches)
        rounds = lm.compare_rounds(system, expected, lr=lr, problems=problems,
                                   moved_share=float(
                                       cell.traffic["check_moved_share"]))
        for row, s in zip(rounds, system):
            row["gdn_neg_beta_share"] = s["gdn_neg_beta_share"]
            if not _exercised(s["gdn_neg_beta_share"]):
                problems.append(
                    f"round {row['round']}: gdn_neg_beta_share "
                    f"{s['gdn_neg_beta_share']!r} is 0 or 1")
        del params, system, expected
        gc.collect()
        out.update(ok=not problems, problems=problems, rounds=rounds,
                   seconds=time.perf_counter() - t0,
                   system_seconds=t_system - t0,
                   forward_seconds=t_forward - t_system,
                   rounds_reference_seconds=time.perf_counter() - t_forward)
        return out


# ----------------------------------------------------------------------
# the comparison that decides ``correct`` for this configuration
# ----------------------------------------------------------------------
#: Tolerances of ``olmo_hybrid_7b_pp8``, and why.  The engine multiplies in
#: bfloat16 (relative rounding 2^-9 per operand) and sums in float32; the
#: reference multiplies in float32 at ``highest``.  The limits are those of
#: ``engines/lm.py``, whose cell runs the same chunked delta rule and its
#: kernels at 128 / 128 (bfloat16 2.1e-2 / 3.8e-2 and float8 e4m3 3.1e-1 /
#: 1.00 there for the logits / a Gated DeltaNet block's gradient).  The
#: limits lie between two readings here, on a v5e at the published widths:
#: what the engine reads over five seeds, and what it reads with every
#: product's operands rounded to float8 e4m3 (``dtype`` of the
#: configuration, ``ops/moe.py:operand``: the nearest precision below the
#: configuration's, which has to fail; one seed), which fails six of the
#: seven comparisons:
#:   LOGITS_RTOL 6e-2: L2 norm of the logits' difference over the norm of
#:     the reference's logits, one minibatch.  bfloat16 1.22e-2 to 1.25e-2;
#:     float8 3.05e-1.
#:   GRAD_RTOL 1.2e-1: L2 norm of the difference of a block's gradient over
#:     the norm of the reference's, for layer 0's Gated DeltaNet block (the
#:     gradient through four layers and the delta rule's backward in three:
#:     bfloat16 5.3e-2 to 6.6e-2) and layer 3's attention block (3.9e-3 to
#:     4.0e-3); float8 1.00 for both.
#:   ``check_moved_share`` 0.05 (traffic file) at ``lm.MOVED_LR`` 0.75: the
#:     share of block 5's elements further than 0.75 lr from the reference
#:     after each of two FedAvg rounds (why a share: ``engines/lm.py``).
#:     bfloat16 0.0132 to 0.0156; float8 0.626 and 0.660.
#:   ``lm.ROUND_LOSS_RTOL`` 5e-4 (``compare_rounds``' own): the round's
#:     summed loss.  bfloat16 2.0e-7 to 3.5e-5; float8 2.1e-4 in round 1 (a
#:     forward pass from the common start) and 4.2e-2 in round 2, which fails.
#:   ``loss_rel`` (printed, NOT judged, as in ``engines/decoder_hc.py``):
#:     round 1's summed loss is the same forward pass from the common start
#:     under a limit of its own.  bfloat16 1.0e-5 to 5.1e-5; float8 5.5e-4.
#:   ``gdn_neg_beta_share`` strictly inside (0, 1) in the forward pass, both
#:     rounds of the check and every round of the run: ``beta`` is float32
#:     in every precision of the products, so it says nothing of them; it
#:     holds the run to exercising transitions with a negative eigenvalue
#:     and transitions without.
#: On a TPU ``gdn_scan_impl`` and ``attn_impl`` must read ``pallas`` in
#: every round.
LOGITS_RTOL = 6e-2
GRAD_RTOL = 1.2e-1
