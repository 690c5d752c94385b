"""VAE and clustering-VAE trainers — engine subclasses.

The reference ships these as two more copies of the driver skeleton
(federated_vae.py, federated_vae_cl.py); here they are small subclasses of
:class:`BlockwiseFederatedTrainer` overriding the workload hooks.

Because they override only the workload hooks, the engine's execution
machinery is inherited wholesale — including ``--fused-rounds`` (the
per-epoch reparametrisation PRNG keys these losses consume are derived
on-device inside the fused round from the same counter-keyed seeds the
host loop uses, so fused VAE rounds stay bit-identical), ``--donate``
buffer donation, ``--async-checkpoint`` background mid-run saves, and
the client-grain flight recorder (``cfg.client_ledger``,
obs/clients.py: the inherited comm round emits per-client ELBO-loss
shares and update norms into `client` records, so the anomaly ranking
and cohort rollup work unchanged on VAE runs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from federated_pytorch_test_tpu.train.engine import BlockwiseFederatedTrainer
from federated_pytorch_test_tpu.train.vae_losses import vae_cl_loss, vae_loss


class VAETrainer(BlockwiseFederatedTrainer):
    """Federated plain VAE (federated_vae.py).

    Differences from the classifier engine, all reproduced:
      * LAYER-wise sweep via unfreeze_one_layer (federated_vae.py:129) while
        ci still ranges over len(train_order_block_ids()) — for
        AutoEncoderCNN both counts are 12, so every layer is visited;
      * loss = sum-MSE + KLD, labels ignored (federated_vae.py:96-108);
      * reparametrisation needs a PRNG key per batch;
      * no L1/L2 regularisation anywhere (no linear_layer_ids test);
      * the reference never evaluates on the test set (loss prints only,
        federated_vae.py:173) — evaluate() here reports per-client test
        ELBO instead (an improvement, flagged in eval_finalize).
    """

    sweep = "layers"
    obs_engine = "vae"

    def sample_init_args(self):
        return super().sample_init_args() + (jax.random.PRNGKey(0),)

    def reg_for_block(self, ci):
        return (0.0, 0.0)

    def model_loss(self, p, bs, xb, yb, wb, rng):
        # wb weights out the pad rows of the wrap-padded final partial
        # minibatch (drop_last=False parity, federated_multi.py:74-83):
        # the sum-reduction ELBO decomposes per sample
        recon, mu, logvar = self.model.apply({"params": p}, xb, rng)
        return vae_loss(recon, xb, mu, logvar, wb), bs

    def eval_batch_metric(self, p, bs, xb, yb, wb):
        # fixed key: deterministic eval ELBO
        recon, mu, logvar = self.model.apply(
            {"params": p}, xb, jax.random.PRNGKey(0))
        return vae_loss(recon, xb, mu, logvar, wb)

    def eval_finalize(self, totals: np.ndarray, n_samples: int) -> np.ndarray:
        return totals / n_samples   # mean test ELBO per sample


class VAECLTrainer(BlockwiseFederatedTrainer):
    """Federated clustering VAE (federated_vae_cl.py).

    * 3-block sweep (encoder / decoder / latent, simple_models.py:430-432);
    * per-block optimizer: latent block (ci==2) -> Adam lr=1e-4; encoder /
      decoder blocks -> LBFGSNew(history_size=10, max_iter=4, batch_mode)
      (federated_vae_cl.py:200-205);
    * reparametrisation ALWAYS active — the reference's disable_repr() is a
      no-op (sets repr_flag=True, simple_models.py:344-345);
    * L2 regularisation lambda2=1e-3 on the flat trainable vector for EVERY
      block (federated_vae_cl.py:228-230), no L1;
    * reference default K=1 (federated_vae_cl.py:12).
    """

    obs_engine = "vae_cl"

    def sample_init_args(self):
        return super().sample_init_args() + (jax.random.PRNGKey(0),)

    def optimizer_for_block(self, ci):
        if ci == 2:                      # latent space block
            return "adam"
        return "lbfgs"

    def lr_for_block(self, ci):
        return 1e-4                      # federated_vae_cl.py:200

    def reg_for_block(self, ci):
        return (0.0, self.cfg.lambda2)   # unconditional L2 (:228-230)

    def model_loss(self, p, bs, xb, yb, wb, rng):
        # wb weights out pad rows; every mean-over-batch divisor in the
        # clustering ELBO becomes sum(wb) = the true partial-batch size
        out = self.model.apply({"params": p}, xb, rng, reparam=True)
        ekhat, mu_xi, sig2_xi, mu_b, sig2_b, mu_th, sig2_th = out
        return vae_cl_loss(ekhat, mu_xi, sig2_xi, mu_b, sig2_b,
                           mu_th, sig2_th, xb, w=wb), bs

    def eval_batch_metric(self, p, bs, xb, yb, wb):
        out = self.model.apply({"params": p}, xb, jax.random.PRNGKey(0),
                               reparam=True)
        ekhat, mu_xi, sig2_xi, mu_b, sig2_b, mu_th, sig2_th = out
        # vae_cl_loss is a per-batch MEAN (divisors are sum(wb)); the eval
        # accumulator sums across batches and eval_finalize divides by the
        # total sample count, so scale back to a per-batch sum here
        return vae_cl_loss(ekhat, mu_xi, sig2_xi, mu_b, sig2_b,
                           mu_th, sig2_th, xb, w=wb) * jnp.sum(wb)

    def eval_finalize(self, totals: np.ndarray, n_samples: int) -> np.ndarray:
        return totals / n_samples        # mean test ELBO per sample
