"""Engine kind ``decoder``: ``LMTrainer`` with any registered decoder,
built from the configuration's own keys and run through its own
``run()``.  The model is ``MODEL_REGISTRY[config["model"]]`` with every
field its class declares that the configuration file sets; the plain
reference is ``benchmarks/reference/<config["model"]>.py``.  The next
decoder needs data files and a reference, and no engine.

What is generic in ``engines/lm.py`` is imported from there
(``Session.__init__``, ``compare_rounds``, ``_WindowClosed``); its
``build_model`` and ``check`` name Qwen3-Next's keys, reference and
one-term loss, and have their counterparts here.

Configuration keys read beside the model's: ``K``, ``batch``,
``seq_len``, ``vocab_rows``, ``lr``, ``dtype``.  Traffic keys read:
``algorithm`` (``fedavg``), ``blocks``, ``Nadmm``, ``Nepoch``,
``samples_per_client``, ``check_moved_share``, ``cfg``.  A sample is one
packed sequence.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import time
from typing import Any, Dict, List

from benchmarks.engines import lm
from benchmarks.lib.cells import Cell
from benchmarks.lib.window import Window


def build_model(config: Dict[str, Any]):
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.models import MODEL_REGISTRY

    cls = MODEL_REGISTRY[config["model"]]
    # flax's own fields (the module's name and parent) are not the model's
    declared = {f.name for f in dataclasses.fields(cls)} \
        - {"name", "parent", "dtype"}
    return cls(dtype=jnp.dtype(config["dtype"]),
               **{k: config[k] for k in sorted(declared) if k in config})


def build_trainer(cell: Cell, seed: int, *, K: int, samples_per_client: int,
                  blocks: List[int], Nloop: int, Nadmm: int, obs_dir=None):
    from federated_pytorch_test_tpu.data.tokens import FederatedTokens
    from federated_pytorch_test_tpu.drivers import federated_multi
    from federated_pytorch_test_tpu.train import FedAvg, LMTrainer

    config, traffic = cell.config, cell.traffic
    if traffic["algorithm"] != "fedavg":
        raise ValueError(f"traffic algorithm {traffic['algorithm']!r}: the "
                         "decoder engine runs 'fedavg'")
    cfg = dataclasses.replace(
        federated_multi.DEFAULTS, K=K, default_batch=int(config["batch"]),
        model=config["model"], lr=float(config["lr"]), Nloop=Nloop,
        Nadmm=Nadmm, Nepoch=int(traffic["Nepoch"]), seed=seed, init_seed=seed,
        num_devices=cell.chips, check_results=False, save_model=False,
        retrace_sentinel=True, obs_dir=obs_dir, **traffic.get("cfg", {}))
    data = FederatedTokens(K, cfg.default_batch, samples_per_client,
                           int(config["seq_len"]), int(config["vocab_rows"]),
                           seed)
    trainer = LMTrainer(build_model(config), cfg, data, FedAvg())
    trainer.block_ids = [trainer.block_ids[b] for b in blocks]
    trainer.L = len(blocks)
    return trainer


class Session(lm.Session):
    def __init__(self, cell: Cell, seed: int, obs_dir=None):
        super().__init__(cell, seed, obs_dir=obs_dir)
        #: the list ``check()`` returns as ``problems``: ``run.py`` reads
        #: it after the window, so the window's own check can add to it
        self.problems: List[str] = []

    # ------------------------------------------------------------------
    def run(self, window: Window) -> None:
        """One ``run()``: the first sweep over the blocks is the untimed
        pass, every later sweep a pass of the window.  After it: the
        multi-token-prediction term of the last pass must be finite and
        not above the untimed pass's."""
        import jax

        t = self.cell.traffic
        trainer = build_trainer(
            self.cell, self.seed, K=int(self.cell.config["K"]),
            samples_per_client=int(t["samples_per_client"]),
            blocks=self.blocks, Nloop=10**9, Nadmm=int(t["Nadmm"]),
            obs_dir=self.obs_dir)
        trainer.obs_run_name = self.cell.name
        records: List[Dict[str, Any]] = []
        dropped = 0

        def on_round(state, rec):
            nonlocal dropped
            dropped += int(rec["moe_dropped"])
            records.append(rec)
            if len(records) == self.rounds_per_pass:
                done = window.pass_done(
                    records, lambda: jax.block_until_ready(state))
                records.clear()
                if done:
                    raise lm._WindowClosed

        try:
            trainer.run(log=lambda msg: None, on_round=on_round)
        except lm._WindowClosed:
            pass
        finally:
            window.abort()
            rec = trainer.obs_recorder
            self.obs_path = getattr(rec, "jsonl_path", None)
            trainer.close()
            self.counters["moe_dropped"] = dropped
        if dropped:
            # a dropped pair is a wrong result, not a slow one: the run
            # must not print a result line that says ``correct``
            raise SystemExit(f"benchmarks/engines/decoder.py: {dropped} "
                             "token-expert pairs found no row (moe_dropped)")
        mtp = lambda recs: sum(r["mtp_loss"] for r in recs)
        first, last = mtp(window.warmup), mtp(window.passes[-1].records)
        impls = sorted({r["attn_impl"] for p in window.passes
                        for r in p.records})
        print(f"mtp_loss: untimed pass {first!r}, last pass {last!r}; "
              f"attn_impl {impls}")
        if not (math.isfinite(last) and last <= first):
            self.problems.append(
                f"mtp_loss of the last pass {last!r} is not finite or lies "
                f"above the untimed pass's {first!r}")

    # ------------------------------------------------------------------
    def check(self) -> Dict[str, Any]:
        """Against the plain reference (``benchmarks/reference/<model>.py``
        through ``decoder_round.py``), at the cell's widths and the timed
        step's shapes:

        (c) two FedAvg rounds of ``trainer.run()`` on the schedule's last
            block, each client's shard ONE minibatch (so the result does
            not depend on the engine's shuffle; round 2 starts from round
            1's write-back), by the share of the block's elements further
            than ``lm.MOVED_LR`` lr from the reference;
        (a) logits and both loss terms of the model on one minibatch;
        (b) the gradient of that minibatch's two-term loss with respect
            to the schedule's first two blocks.

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
        system = []

        def on_round(state, rec):
            system.append({
                "x": [jnp.copy(ref.get_path(state.params, p)) for p in paths],
                "loss": rec["loss"], "moe_dropped": rec["moe_dropped"]})

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
        grad_blocks = self.blocks[:2]
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
        mtp = float(jnp.mean(aux["mtp_loss"]))
        want_loss = want_mtp = err2 = ref2 = 0.0
        want_grads = None
        for i in range(batch):
            l, seen, g = ref.loss_and_grad(cell.config, params, gpaths,
                                           ids[i], labels[i])
            want_loss += float(l) / batch
            want_mtp += float(seen["mtp_loss"]) / batch
            err2 += float(jnp.sum((logits[i] - seen["logits"]) ** 2))
            ref2 += float(jnp.sum(seen["logits"] ** 2))
            g = [gi / batch for gi in g]
            want_grads = g if want_grads is None else [
                a + b for a, b in zip(want_grads, g)]
            del seen
        out["loss_rel"] = abs(float(loss) - want_loss) / abs(want_loss)
        out["mtp_loss_rel"] = abs(mtp - want_mtp) / abs(want_mtp)
        out["logits_rel"] = (err2 / ref2) ** 0.5
        for name, limit in (("loss_rel", LOSS_RTOL),
                            ("mtp_loss_rel", LOSS_RTOL),
                            ("logits_rel", LOGITS_RTOL)):
            if not out[name] <= limit:
                problems.append(f"forward: {name} {out[name]:.2e} > {limit} "
                                f"(loss {float(loss)!r} vs reference "
                                f"{want_loss!r}, mtp_loss {mtp!r} vs "
                                f"{want_mtp!r})")
        if int(aux["moe_dropped"]):
            problems.append(f"forward: {int(aux['moe_dropped'])} token-"
                            "expert pairs dropped")
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
#: Tolerances of ``glm47flash_30b_a3b_ep8``, and why.  The engine multiplies
#: in bfloat16 (relative rounding 2^-9 per operand) and sums in float32;
#: the reference multiplies in float32.  Each limit lies between two
#: readings on the chip at the published widths (my chip runs, PR 32, the
#: committed files): what the engine reads over its seeds (four seeds), and
#: what it reads with every product's operands rounded to float8 e4m3
#: (``dtype`` of the configuration, ``ops/moe.py:operand``; the nearest
#: precision below the configuration's), which has to fail.
#:   LOGITS_RTOL 6e-2: L2 norm of the logits' difference over the norm of
#:     the reference's logits, one minibatch.  bfloat16 1.24e-2 to 1.78e-2;
#:     float8 2.29e-1.
#:   GRAD_RTOL 1e-1: L2 norm of the difference of a block's gradient over
#:     the norm of the reference's, for layer 1's latent attention
#:     (bfloat16 4.7e-3 to 5.0e-3) and layer 2's expert block (9.9e-3 to
#:     2.06e-2: few tokens reach a held expert, and the reading moves with
#:     which do); float8 1.00 for both (a gradient of 1e-6 rounds to 0 in
#:     e4m3).
#:   ``check_moved_share`` 0.03 (traffic file) at ``lm.MOVED_LR`` 0.75: the
#:     share of ``mtp_mixer``'s elements further than 0.75 lr from the
#:     reference after each of two FedAvg rounds (why a share:
#:     ``engines/lm.py``).  bfloat16 1.4e-3 to 2.0e-3; float8 0.498 and
#:     0.602.
#:   ``lm.ROUND_LOSS_RTOL`` 5e-4 (``compare_rounds``' own): the round's
#:     summed two-term loss.  bfloat16 4.8e-7 to 1.0e-5; float8 3.1e-4 in
#:     round 1 (a forward pass from the common start, which hardly moves
#:     with precision) and 1.34e-3 in round 2, which fails.
#:   LOSS_RTOL 2e-3: the forward pass's two-term loss and its MTP term; the
#:     roundings of 8,192 tokens' logits average out (bfloat16 1.8e-6 to
#:     2.2e-5 and 2.9e-6 to 1.3e-5; float8 6.1e-4 and 1.9e-4), so this one
#:     does not discriminate between precisions and is a bound on gross
#:     faults only: a dropped layer, a wrong mask or a wrong target moves
#:     either term by percents.
#: ``moe_dropped`` must be 0 in the forward pass and in every round.
LOGITS_RTOL = 6e-2
LOSS_RTOL = 2e-3
GRAD_RTOL = 1e-1
