"""Engine kind ``lm``: ``LMTrainer`` (the blockwise engine with a token
batch and a decoder) built from the configuration's own keys, run
through its own ``run()``.

Configuration keys read: the published ``qwen3_next`` keys (``models/
qwen3_next.py:Qwen3Next`` takes them by name), the cut (``layers``,
``experts_held``, ``ep_rank``, ``vocab_rows``), ``K``, ``batch``,
``seq_len``, ``lr``, ``dtype``, ``pair_rows_factor``.  Traffic keys read:
``algorithm`` (``fedavg``), ``blocks``, ``Nadmm``, ``Nepoch``,
``samples_per_client``, ``check_moved_share``, ``cfg``.  A sample is one
packed sequence.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.lib.cells import Cell
from benchmarks.lib.window import Window

#: ``Qwen3Next`` fields a configuration file may set, by its own key
MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "rms_norm_eps",
    "full_attention_interval", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "moe_intermediate_size",
    "shared_expert_intermediate_size", "layers", "experts_held", "ep_rank",
    "vocab_rows", "pair_rows_factor", "chunk", "attn_block")


class _WindowClosed(Exception):
    """Raised from ``on_round`` at the pass boundary that ends the window."""


def build_model(config: Dict[str, Any]):
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.models import MODEL_REGISTRY

    fields = {k: config[k] for k in MODEL_KEYS if k in config}
    return MODEL_REGISTRY[config["model"]](
        dtype=jnp.dtype(config["dtype"]), **fields)


def build_trainer(cell: Cell, seed: int, *, K: int, samples_per_client: int,
                  blocks: List[int], Nloop: int, Nadmm: int, obs_dir=None):
    from federated_pytorch_test_tpu.data.tokens import FederatedTokens
    from federated_pytorch_test_tpu.drivers import federated_multi
    from federated_pytorch_test_tpu.train import FedAvg, LMTrainer

    config, traffic = cell.config, cell.traffic
    if traffic["algorithm"] != "fedavg":
        raise ValueError(f"traffic algorithm {traffic['algorithm']!r}: the "
                         "lm engine runs 'fedavg'")
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


class Session:
    def __init__(self, cell: Cell, seed: int, obs_dir=None):
        self.cell, self.seed, self.obs_dir = cell, seed, obs_dir
        t = cell.traffic
        self.blocks = [int(b) for b in t["blocks"]]
        self.rounds_per_pass = len(self.blocks) * int(t["Nadmm"])
        self.samples_per_round = (int(cell.config["K"]) * int(t["Nepoch"])
                                  * int(t["samples_per_client"]))
        self.samples_per_pass = self.rounds_per_pass * self.samples_per_round
        self.obs_path = None
        self.counters: Dict[str, float] = {}

    # ------------------------------------------------------------------
    def run(self, window: Window) -> None:
        """One ``run()``: the first sweep over the blocks is the untimed
        pass, every later sweep a pass of the window."""
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
                    raise _WindowClosed

        try:
            trainer.run(log=lambda msg: None, on_round=on_round)
        except _WindowClosed:
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
            raise SystemExit(f"benchmarks/engines/lm.py: {dropped} token-"
                             "expert pairs found no row (moe_dropped)")

    # ------------------------------------------------------------------
    def check(self) -> Dict[str, Any]:
        """Against the plain reference (``benchmarks/reference/
        qwen3_next.py``, ``lm_round.py``), at the cell's widths and the
        timed step's shapes:

        (c) two FedAvg rounds of ``trainer.run()`` on the schedule's last
            block, each client's shard ONE minibatch (so the result does
            not depend on the engine's shuffle; round 2 starts from round
            1's write-back), by the share of the block's elements further
            than ``MOVED_LR`` lr from the reference;
        (a) loss and logits of the model's forward on one minibatch;
        (b) the gradient of that minibatch's loss with respect to a
            Gated DeltaNet block and an expert block (the schedule's
            first two blocks).

        (a) and (b) are one program (the model's loss differentiated with
        respect to both blocks); the reference goes sequence by sequence
        so that it fits beside it.
        """
        import jax
        import jax.numpy as jnp

        from benchmarks.reference import lm_round, qwen3_next as ref
        from federated_pytorch_test_tpu.models.qwen3_next import (
            next_token_loss,
        )

        cell, t0 = self.cell, time.perf_counter()
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

        problems, out = [], {}
        # (a), (b): one minibatch, client 0's
        ids, labels = jnp.asarray(xs[0]), jnp.asarray(ys[0])
        grad_blocks = self.blocks[:2]
        gpaths = [p for b in grad_blocks for p in paths_of(b)]

        def system_loss(leaves, p, ids, labels):
            for path, leaf in zip(gpaths, leaves):
                p = ref.set_path(p, path, leaf)
            logits, aux = model.apply({"params": p}, ids)
            return next_token_loss(logits, labels), (logits, aux)

        (loss, (logits, aux)), grads = jax.jit(
            jax.value_and_grad(system_loss, has_aux=True))(
                [ref.get_path(params, p) for p in gpaths], params, ids,
                labels)
        want_loss, err2, ref2 = 0.0, 0.0, 0.0
        want_grads = None
        for i in range(batch):
            l, lg, g = ref.loss_and_grad(cell.config, params, gpaths, ids[i],
                                         labels[i])
            want_loss += float(l) / batch
            err2 += float(jnp.sum((logits[i] - lg) ** 2))
            ref2 += float(jnp.sum(lg * lg))
            g = [gi / batch for gi in g]
            want_grads = g if want_grads is None else [
                a + b for a, b in zip(want_grads, g)]
        out["loss_rel"] = abs(float(loss) - want_loss) / abs(want_loss)
        out["logits_rel"] = (err2 / ref2) ** 0.5
        if not out["loss_rel"] <= LOSS_RTOL:
            problems.append(f"forward: loss {float(loss)!r} vs reference "
                            f"{want_loss!r} (rel {out['loss_rel']:.2e} > "
                            f"{LOSS_RTOL})")
        if not out["logits_rel"] <= LOGITS_RTOL:
            problems.append(f"forward: logits differ from the reference by "
                            f"{out['logits_rel']:.2e} of their norm "
                            f"(> {LOGITS_RTOL})")
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
        expected = lm_round.run_rounds(cell.config, params, paths, lr,
                                       batches)
        rounds = compare_rounds(system, expected, lr=lr, problems=problems,
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
# the comparison that decides ``correct`` for this engine
# ----------------------------------------------------------------------
#: Tolerances, and why.  The engine multiplies in bfloat16 (8 bits of
#: mantissa, relative rounding 2^-9 = 2e-3 per operand) and sums in
#: float32; the reference multiplies in float32.  Errors of independent
#: roundings add in quadrature over a contraction and compound over the
#: four layers and their backward passes.  Each limit lies between two
#: readings on the chip at the published widths (my chip runs, PR 27):
#: what the engine reads over its seeds (three seeds), and what it reads
#: with every product's operands rounded to float8 e4m3 (4 bits; the
#: nearest precision below the configuration's; ``dtype`` of the
#: configuration, ``ops/moe.py:operand``), which has to fail.
#:   LOGITS_RTOL 6e-2: L2 norm of the logits' difference over the norm
#:     of the reference's logits, one minibatch.  bfloat16 2.14e-2 to
#:     2.20e-2; float8 3.11e-1.
#:   GRAD_RTOL 1.2e-1: L2 norm of the difference of a block's gradient
#:     over the norm of the reference's, for the Gated DeltaNet block
#:     (bfloat16 3.73e-2 to 3.84e-2) and the expert block (3.89e-2 to
#:     4.07e-2); float8 1.00 for both (a gradient of 1e-6 rounds to 0 in
#:     e4m3).  Rows of the pair buffer past the last expert's group read
#:     stale memory on the TPU unless zeroed: that read 6e6 and 1.7e9
#:     here before ``ops/moe.py`` zeroed them.
#:   MOVED_LR 0.75, ``check_moved_share`` 0.05 (traffic file): as
#:     engines/classifier.py words it: Adam's first steps are lr x
#:     sign(g), so an element whose gradient is smaller than the bfloat16
#:     noise lands 2 lr from the reference however exact the engine is;
#:     what discriminates is the SHARE of elements further than 0.75 lr.
#:     With K = 2 a FedAvg mean hides one client's flip only by half
#:     (lr: counted), so the share is higher than the classifier's at 4
#:     clients per chip.  bfloat16 0.0135 to 0.0230 over rounds and
#:     seeds; float8 0.565 and 0.654.
#:   ROUND_LOSS_RTOL 5e-4: the round's summed loss.  bfloat16 4.6e-6 to
#:     1.4e-5; float8 2.28e-3 in round 2 (round 1's loss is a forward
#:     pass from the common start and hardly moves with precision).
#:   LOSS_RTOL 2e-3: the mean cross-entropy of the forward pass; the
#:     roundings of 8,192 tokens' logits average out (bfloat16 7.4e-6 to
#:     1.6e-5, float8 4.5e-5), so this one does not discriminate between
#:     precisions and is a bound on gross faults only: a dropped layer or
#:     a wrong mask moves the loss by percents.
#: ``moe_dropped`` must be 0 in the forward pass and in every round.
MOVED_LR = 0.75
LOGITS_RTOL = 6e-2
LOSS_RTOL = 2e-3
GRAD_RTOL = 1.2e-1
ROUND_LOSS_RTOL = 5e-4


def compare_rounds(system, expected, *, lr: float, moved_share: float,
                   problems: List[str]) -> List[Dict[str, Any]]:
    """``system[r]["x"]`` are ``[K, ...]`` stacked block leaves after
    round ``r``; ``expected[r]["x"][k]`` the reference's leaves of client
    ``k``.  Appends to ``problems``; returns one row per round."""
    import jax.numpy as jnp

    rounds = []
    if len(system) != len(expected):
        problems.append(f"engine ran {len(system)} rounds, reference "
                        f"{len(expected)}")
    for r, (s, e) in enumerate(zip(system, expected), start=1):
        moved, worst, n = 0, 0.0, 0
        for i, leaf in enumerate(s["x"]):
            d = jnp.abs(leaf.astype(jnp.float32)
                        - jnp.stack([w[i] for w in e["x"]]))
            moved += int(jnp.sum(d > MOVED_LR * lr))
            worst = max(worst, float(jnp.max(d)))
            n += int(leaf.size)
        row = {"round": r, "moved_share": moved / n,
               "max_move_lr": worst / lr, "loss": s["loss"],
               "loss_ref": e["loss"],
               "loss_rel": abs(s["loss"] - e["loss"]) / abs(e["loss"])}
        if not np.isfinite(worst) or row["moved_share"] > moved_share:
            problems.append(
                f"round {r}: {row['moved_share']:.5f} of the block's "
                f"elements are further than {MOVED_LR} lr from the reference "
                f"(bound {moved_share})")
        if not row["loss_rel"] <= ROUND_LOSS_RTOL:
            problems.append(
                f"round {r}: loss {s['loss']!r} vs reference {e['loss']!r} "
                f"(rel {row['loss_rel']:.2e} > {ROUND_LOSS_RTOL})")
        if s["moe_dropped"]:
            problems.append(f"round {r}: {s['moe_dropped']} token-expert "
                            "pairs dropped")
        rounds.append(row)
    return rounds
