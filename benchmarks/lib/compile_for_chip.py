"""Compile a classifier cell's epoch program at its real size for chips
that are described and not attached (on-chip-measurement guide, section
2): what the TPU compiler refuses, and what the program needs on each
chip, before any chip time is spent.

The engine builds its mesh from ``jax.devices()`` and places its own
parameters, so it cannot be constructed on described devices.  It is
built here on the CPU at the smallest size with the same shapes per
client, then handed the described mesh before its step functions are
built; the program is lowered from shapes, never arrays.  That reaches
into the engine (``_build_fns``, ``mesh``) and is for rehearsal only.
"""

from __future__ import annotations

import dataclasses

from benchmarks.lib.cells import Cell


def compile_epoch(cell: Cell, devices, block: int = -1):
    """``jax.stages.Compiled`` of ``epoch_shard`` for the cell's
    ``blocks[block]`` at the cell's K, batch and samples per client,
    sharded over ``devices`` (described TPU devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmarks.engines import classifier
    from federated_pytorch_test_tpu.train.engine import ClientState

    t = cell.traffic
    K, D, batch = int(cell.config["K"]), len(devices), int(cell.config["batch"])
    small = dataclasses.replace(
        cell, chips=1, traffic={**t, "cfg": {**t.get("cfg", {}),
                                             "cost_ledger": False}})
    trainer = classifier.build_trainer(
        small, 0, K=1, samples_per_client=batch,
        blocks=[int(t["blocks"][block])], Nloop=1, Nadmm=1)
    trainer._sentinel = None
    trainer.mesh = mesh = Mesh(np.asarray(devices), ("clients",))
    trainer.D = D
    trainer.cfg = dataclasses.replace(trainer.cfg, K=K)
    trainer._donate = True                  # as on an accelerator backend
    train_epoch, _, init_opt = trainer._build_fns(0)
    trainer.close()

    csh = NamedSharding(mesh, PartitionSpec("clients"))
    rsh = NamedSharding(mesh, PartitionSpec())
    S = jax.ShapeDtypeStruct
    per_client = lambda tree: jax.tree.map(
        lambda a: S((K,) + a.shape[1:], a.dtype, sharding=csh), tree)
    params = per_client(trainer.params0)
    opt = jax.tree.map(
        lambda a: S(a.shape, a.dtype,
                    sharding=csh if a.ndim and a.shape[0] == K else rsh),
        jax.eval_shape(init_opt, params))
    state = ClientState(params, per_client(trainer.batch_stats0), opt, ())
    N = trainer.block_size(0)
    steps = int(t["samples_per_client"]) // batch
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), K))
    args = (state,
            S((K, N if trainer.algo.needs_dual else 1), jnp.float32,
              sharding=csh),                                    # y
            S((K, 2, 3), jnp.float32, sharding=csh),            # norm
            S(keys.shape, keys.dtype, sharding=csh),
            S((K, steps, batch, 32, 32, 3), jnp.uint8, sharding=csh),
            S((K, steps, batch), jnp.int32, sharding=csh),
            S((K, steps, batch), jnp.float32, sharding=csh),
            S((N,), jnp.float32, sharding=rsh),                 # z
            S((), jnp.float32, sharding=rsh),                   # rho
            S((K,), jnp.float32, sharding=csh))                 # active
    return train_epoch.lower(*args).compile()
