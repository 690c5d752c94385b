"""Isolate which piece of the CPC graph compiles pathologically on TPU.

Usage: python artifacts/probe_cpc_compile.py <piece> <Lc> [batch]

Pieces: enc_fwd, enc_grad, stem_fwd, stem_grad, trunk_fwd, trunk_grad,
        full_fwd, full_grad
Each run jits ONE piece and prints the compile wall-clock; the caller
bounds it with a subprocess timeout so a >20 min pathological compile
just shows up as a kill.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from federated_pytorch_test_tpu.models.cpc import (
    ContextgenCNN,
    EncoderCNN,
    PredictorCNN,
)
from federated_pytorch_test_tpu.utils.compile_cache import (
    enable_persistent_compile_cache,
)

piece = sys.argv[1]
Lc = int(sys.argv[2])
batch = int(sys.argv[3]) if len(sys.argv) > 3 else 128
Rc = 32

rng = jax.random.PRNGKey(0)
x = jnp.asarray(np.random.default_rng(0).normal(size=(batch, 32, 32, 8)),
                jnp.float32)

enc = EncoderCNN(latent_dim=Lc)
enc_p, _ = enc.init_variables(rng, x)


import flax.linen as _nn


class Stem(EncoderCNN):
    """Just the five dilated convs + concat."""

    @_nn.compact
    def __call__(self, x, train=True):  # noqa: D102
        import flax.linen as nn

        from federated_pytorch_test_tpu.models.base import elu
        from federated_pytorch_test_tpu.models.cpc import _pad
        xs = []
        for d, p in ((1, 1), (2, 3), (4, 6), (8, 12), (16, 24)):
            xs.append(elu(nn.Conv(8, (4, 4), strides=(2, 2),
                                  kernel_dilation=(d, d), padding=_pad(p),
                                  name=f"conv1_{d}")(x)))
        return jnp.concatenate(xs, axis=-1)


class Trunk(EncoderCNN):
    """conv2..conv4 + pool on a pre-made [B,16,16,40] input."""

    @_nn.compact
    def __call__(self, x, train=True):  # noqa: D102
        import flax.linen as nn

        from federated_pytorch_test_tpu.models.base import elu
        from federated_pytorch_test_tpu.models.cpc import _pad
        x = elu(nn.Conv(self.latent_dim // 4, (4, 4), strides=(2, 2),
                        padding=_pad(1), name="conv2")(x))
        x = elu(nn.Conv(self.latent_dim // 2, (4, 4), strides=(2, 2),
                        padding=_pad(1), name="conv3")(x))
        x = elu(nn.Conv(self.latent_dim, (4, 4), strides=(2, 2),
                        padding=_pad(1), name="conv4")(x))
        x = nn.avg_pool(x, window_shape=(2, 2), strides=(2, 2))
        return x.reshape((x.shape[0], -1))


def timed(tag, fn, *args):
    enable_persistent_compile_cache()
    t0 = time.perf_counter()
    r = jax.block_until_ready(jax.jit(fn)(*args))
    # a host fetch closes out the dispatch either way
    jax.tree.map(np.asarray, r)
    print(f"{tag}: compile+run {time.perf_counter() - t0:.1f}s",
          flush=True)


if piece == "enc_fwd":
    timed(f"enc_fwd Lc={Lc} B={batch}",
          lambda p, x: enc.apply({"params": p}, x), enc_p, x)
elif piece == "enc_grad":
    timed(f"enc_grad Lc={Lc} B={batch}",
          jax.grad(lambda p, x: enc.apply({"params": p}, x).sum()), enc_p, x)
elif piece in ("stem_fwd", "stem_grad"):
    stem = Stem(latent_dim=Lc)
    sp, _ = stem.init_variables(rng, x)
    f = lambda p, x: stem.apply({"params": p}, x)  # noqa: E731
    if piece == "stem_grad":
        f = jax.grad(lambda p, x: stem.apply({"params": p}, x).sum())
    timed(f"{piece} Lc={Lc} B={batch}", f, sp, x)
elif piece in ("trunk_fwd", "trunk_grad"):
    trunk = Trunk(latent_dim=Lc)
    xt = jnp.zeros((batch, 16, 16, 40), jnp.float32)
    tp, _ = trunk.init_variables(rng, xt)
    f = lambda p, x: trunk.apply({"params": p}, x)  # noqa: E731
    if piece == "trunk_grad":
        f = jax.grad(lambda p, x: trunk.apply({"params": p}, x).sum())
    timed(f"{piece} Lc={Lc} B={batch}", f, tp, xt)
elif piece in ("full_fwd", "full_grad"):
    # encoder -> grid reshape -> contextgen -> predictor -> InfoNCE
    from federated_pytorch_test_tpu.ops.infonce import info_nce_fused

    ctx = ContextgenCNN(latent_dim=Lc)
    pred = PredictorCNN(latent_dim=Lc, reduced_dim=Rc)
    px = py = 4
    lat0 = jnp.zeros((batch // (px * py), px, py, Lc), jnp.float32)
    ctx_p, _ = ctx.init_variables(rng, lat0)
    pred_p, _ = pred.init_variables(rng, lat0, lat0)

    def loss(params, x):
        ep, cp, pp = params
        lat = enc.apply({"params": ep}, x)
        lat = lat.reshape((-1, px, py, Lc))
        c = ctx.apply({"params": cp}, lat)
        rl, pr = pred.apply({"params": pp}, lat, c)
        return info_nce_fused(rl, pr)

    f = loss if piece == "full_fwd" else jax.grad(loss)
    timed(f"{piece} Lc={Lc} B={batch}", f, (enc_p, ctx_p, pred_p), x)
else:
    raise SystemExit(f"unknown piece {piece}")
