"""Compile a decoder cell's epoch programs at their real size for a chip
that is described and not attached (on-chip-measurement guide, section
2), as ``compile_for_chip.py`` does for the classifier: what the TPU
compiler refuses and what each program needs, before any chip time is
spent.

    JAX_PLATFORMS=cpu python -m benchmarks.lib.compile_decoder_for_chip \\
        xing4_fedavg_mixer_blocks [index into the traffic's blocks ...]

The trainer is built here on the CPU with ONE client's weights (3 GB of
host memory at 759 M parameters) and handed the described device before
its step functions are built; the program is lowered from shapes, never
arrays, with the attention kernels forced (``plan()`` asks the backend,
which is the CPU here).  That reaches into the engine (``_build_fns``,
``mesh``) and is for rehearsal only.  The runtime needs more than the
count printed: the GLM-4.7-Flash and Xing4.0 cells' live buffers were
2.2 and 3.4 GiB above the arguments (the check's leftovers, the
exchange), their pools the temporaries.
"""

from __future__ import annotations

import dataclasses
import sys
import time

from benchmarks.lib.cells import Cell, load_cell


def compile_epoch(cell: Cell, device, block: int = -1):
    """``jax.stages.Compiled`` of the epoch program for the cell's
    ``blocks[block]`` at the cell's K, batch, sequence length and samples
    per client on ``device`` (a described TPU device)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmarks.engines import decoder
    from federated_pytorch_test_tpu.ops import flash_attention, gated_delta
    from federated_pytorch_test_tpu.train.engine import ClientState

    t = cell.traffic
    K, batch = int(cell.config["K"]), int(cell.config["batch"])
    T = int(cell.config["seq_len"])
    small = dataclasses.replace(
        cell, chips=1, traffic={**t, "cfg": {**t.get("cfg", {}),
                                             "cost_ledger": False}})
    trainer = decoder.build_trainer(
        small, 0, K=1, samples_per_client=batch,
        blocks=[int(t["blocks"][block])], Nloop=1, Nadmm=1)
    trainer._sentinel = None
    trainer.mesh = mesh = Mesh(np.asarray([device]), ("clients",))
    trainer.D = 1
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
    ids = S((K, steps, batch, T), jnp.int32, sharding=csh)
    args = (state,
            S((K, N if trainer.algo.needs_dual else 1), jnp.float32,
              sharding=csh),                                    # y
            S((K, 1), jnp.float32, sharding=csh),               # norm
            S(keys.shape, keys.dtype, sharding=csh),
            ids, ids,                                           # ids, labels
            S((K, steps, batch), jnp.float32, sharding=csh),
            S((N,), jnp.float32, sharding=rsh),                 # z
            S((), jnp.float32, sharding=rsh),                   # rho
            S((K,), jnp.float32, sharding=csh))                 # active
    del trainer
    with flash_attention.force_attn_impl("pallas"), \
            gated_delta.force_gdn_scan_impl("pallas"):
        lowered = train_epoch.lower(*args)
    return lowered.compile()


def main(argv) -> int:
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    cell = load_cell(argv[0])
    blocks = [int(b) for b in argv[1:]] or range(len(cell.traffic["blocks"]))
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    gib = lambda b: round(b / 2**30, 2)
    for b in blocks:
        t0 = time.perf_counter()
        m = compile_epoch(cell, device, b).memory_analysis()
        print(f"block {cell.traffic['blocks'][b]}: arguments "
              f"{gib(m.argument_size_in_bytes)} + temporaries "
              f"{gib(m.temp_size_in_bytes)} GiB (outputs "
              f"{gib(m.output_size_in_bytes)}, of them aliased "
              f"{gib(m.alias_size_in_bytes)}), "
              f"{time.perf_counter() - t0:.0f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
