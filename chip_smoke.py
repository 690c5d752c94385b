#!/usr/bin/env python3
"""Chip smoke: does the blockwise trainer start, train and exit correctly
on the TPU — one process, the normal entry points, full ResNet18 width.

    python3 chip_smoke.py

No arguments.  Exit code 0 and a last stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

only when every phase passed on a TPU backend; any other outcome (no
accelerator, a phase that raised or failed a check, the package missing
next to this file) exits non-zero and prints no result line.  Small
outputs (the run's obs JSONL, a summary) land in ``chiprun_out/chip_smoke/``;
the end-of-run checkpoint is written under ``checkpoints/chip_smoke/``,
read back and removed.  Both directories are git-ignored.

Phases (each an importable function with size arguments, so
tests/test_chip_smoke.py runs them tiny on the CPU mesh with the kernels
in interpret mode; only :func:`main` demands the chip):

- ``train``      the consensus driver's ``main(argv)`` on ResNet18 bf16,
                 batch 128, all ten blocks, K = 8 x device count
- ``parity``     largest block, f32 "highest": TPU mesh vs a CPU mesh in
                 the same process through the engine's ``mesh=`` argument
- ``mesh``       (device count > 1) K/D client rows on every device, and
                 the D-device round allclose to the 1-device round
- ``kernels``    the five Pallas entry points compiled (never interpret)
                 against their XLA paths at driver shapes, and what
                 auto-dispatch resolves to
- ``compressed`` one round each with q8 + fused collective, top-k, and
                 chunked krum on the largest block

Sizes: depth and rounds are cut (Nloop 1, Nadmm 2, 1024 samples per
client); width, batch and the block partition are the reference's.
Weights are random from the config seed, data is the seeded synthetic
CIFAR stand-in (the driver banner prints ``data=``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
CKPT_DIR = os.path.join(REPO, "checkpoints", "chip_smoke")

#: reference ResNet18 partition [54, 59] — the largest block of the sweep
BIG_BLOCK_N = 4_720_640
CHANCE_PCT = 10.0


class SmokeFailure(AssertionError):
    """A phase ran to its end and a check on its output failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _finite(x) -> bool:
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(x, np.float64))))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


# ----------------------------------------------------------------------
# train: the normal driver, full width
# ----------------------------------------------------------------------
def phase_train(K: int, *, model_argv=("--use-resnet", "--bf16"),
                batch: int = 128, n_train: int = 1024, n_test: int = 2048,
                Nloop: int = 1, Nadmm: int = 2, platform: str = "tpu",
                min_accuracy: float = 1.5 * CHANCE_PCT,
                out_dir: str = OUT_DIR, ckpt_dir: str = CKPT_DIR) -> dict:
    import jax
    import numpy as np

    from federated_pytorch_test_tpu.drivers import consensus_multi
    from federated_pytorch_test_tpu.obs.report import read_records
    from federated_pytorch_test_tpu.utils.checkpoint import (
        load_checkpoint,
        verify_checkpoint,
    )

    obs_dir = os.path.join(out_dir, "obs")
    shutil.rmtree(obs_dir, ignore_errors=True)     # the JSONL sink appends
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    check(Nadmm >= 2, "the no-recompile check needs a second comm round")
    state, history = consensus_multi.main([
        *model_argv, "--K", str(K), "--default-batch", str(batch),
        "--Nloop", str(Nloop), "--Nadmm", str(Nadmm),
        "--n-train", str(n_train), "--n-test", str(n_test),
        "--retrace-sentinel", "--checkpoint-dir", ckpt_dir,
        "--obs-dir", obs_dir])

    D = len(jax.devices())
    records = read_records(os.path.join(obs_dir, "consensus_multi.jsonl"))
    header = records[0]
    rounds = [r for r in records if r["event"] == "round"]
    check(header["mesh_shape"] == {"clients": D},
          f"trainer.D != device count: mesh {header['mesh_shape']}, "
          f"{D} devices (the mesh shrank)")
    check(len(rounds) == len(history) > 0, "obs JSONL and history disagree")
    for r in history:
        vals = [r["loss"], r["dual_residual"], r["primal_residual"]]
        check(_finite(vals), f"non-finite round record: block {r['block']} "
                             f"nadmm {r['nadmm']}: {vals}")
        check(r["jit_retraces"] == 0,
              f"retrace sentinel tripped at block {r['block']} "
              f"nadmm {r['nadmm']}: {r['jit_retraces']}")
        if r["nadmm"] >= 1:
            check("compile_seconds" not in r,
                  f"block {r['block']} comm round {r['nadmm']} compiled "
                  f"({r.get('compile_seconds')} s): the warm round is not "
                  "warm")
    acc = float(np.mean(history[-1]["accuracy"]))
    check(acc > min_accuracy,
          f"mean accuracy {acc:.2f}% after the sweep is not above chance "
          f"({CHANCE_PCT}%; bound {min_accuracy}%)")

    leaves = jax.tree.leaves(state)
    off = [d for leaf in leaves for d in leaf.devices()
           if d.platform != platform]
    check(not off, f"state leaves off {platform}: {sorted(set(map(str, off)))}")
    check(all(len(leaf.sharding.device_set) == D for leaf in leaves),
          "a state leaf does not span every device")

    path = os.path.join(ckpt_dir, "consensus_multi")
    check(verify_checkpoint(path), f"checkpoint {path} has no checksum")
    restored, meta = load_checkpoint(path)
    check(int(meta["rounds"]) == len(history), f"checkpoint meta {meta}")
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(state.params)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "checkpoint params do not read back bit-equal")
    del restored
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    print("block        N   cold_compile_s  warm_round_s")
    blocks = []
    for ci in sorted({r["block"] for r in rounds}):
        cold = sum(r.get("compile_seconds", 0.0) for r in rounds
                   if r["block"] == ci and r["nadmm"] == 0)
        warm = [r["round_seconds"] for r in rounds
                if r["block"] == ci and r["nadmm"] >= 1]
        N = next(r["N"] for r in rounds if r["block"] == ci)
        blocks.append({"block": ci, "N": N, "cold_compile_s": cold,
                       "warm_round_s": min(warm)})
        print(f"{ci:5d} {N:8d}   {cold:14.2f}  {min(warm):12.4f}")
    return {"K": K, "D": D, "rounds": len(history), "accuracy_mean": acc,
            "loss_final": history[-1]["loss"], "blocks": blocks}


# ----------------------------------------------------------------------
# one consensus round on the largest block, through the engine's own loop
# ----------------------------------------------------------------------
def block_round(mesh, K: int, *, batch: int = 128, steps: int = 2,
                Nadmm: int = 1, bf16: bool = False, precision=None,
                model: str = "resnet18", **cfg_kw):
    """Nadmm comm rounds of the consensus engine on the model's LARGEST
    block only.  Returns ``(trainer, state, history)``; the trainer is
    closed but its mesh / staged data stay inspectable."""
    import jax
    import numpy as np

    from federated_pytorch_test_tpu.data.cifar10 import FederatedCifar10
    from federated_pytorch_test_tpu.drivers import common, consensus_multi
    from federated_pytorch_test_tpu.train import (
        AdmmConsensus,
        BlockwiseFederatedTrainer,
    )

    cfg = dataclasses.replace(
        consensus_multi.DEFAULTS, K=K, default_batch=batch, Nloop=1,
        Nadmm=Nadmm, model=model, bf16=bf16, check_results=False,
        save_model=False, retrace_sentinel=True, **cfg_kw)
    data = FederatedCifar10(K=K, batch=batch, biased_input=cfg.biased_input,
                            limit_per_client=steps * batch, limit_test=batch)
    trainer = BlockwiseFederatedTrainer(common.pick_model(cfg), cfg, data,
                                        AdmmConsensus(), mesh=mesh)
    # sweep one unit: the partition entry with the most parameters
    big = int(np.argmax([trainer.block_size(ci) for ci in range(trainer.L)]))
    trainer.block_ids = [trainer.block_ids[big]]
    trainer.L = 1
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        state, history = trainer.run(log=lambda msg: None)
    trainer.close()
    for r in history:
        check(_finite([r["loss"], r["dual_residual"], r["primal_residual"]]),
              f"non-finite block round: {r}")
        check(r["jit_retraces"] == 0, f"block round retraced: {r}")
    return trainer, state, history


def _round_summary(history) -> dict:
    r = history[-1]
    return {k: float(r[k]) for k in
            ("loss", "dual_residual", "primal_residual", "N")}


def phase_parity(K: int, *, cpu_devices=None, rtol: float = 1e-3,
                 **size_kw):
    """The default-backend mesh against a CPU mesh, f32 at "highest"
    matmul precision: the first check that donation-on, device-resident
    staging and the accelerator lowering compute what the CPU tests
    check.  The loss is mostly the consensus penalty at this N, so the
    residuals carry the comparison of the update itself: primal is the
    spread of the clients' Adam steps, dual the collective's output.
    Returns ``(result, accelerator_run)`` — the mesh phase compares
    against the same accelerator run."""
    import jax

    from federated_pytorch_test_tpu.parallel.mesh import client_mesh

    cpus = jax.devices("cpu") if cpu_devices is None else cpu_devices
    dev_run = block_round(client_mesh(), K, precision="highest", **size_kw)
    d_cpu = max(d for d in range(1, len(cpus) + 1) if K % d == 0)
    cpu_run = block_round(client_mesh(d_cpu, cpus), K, precision="highest",
                          **size_kw)
    a, b = _round_summary(dev_run[2]), _round_summary(cpu_run[2])
    out = {"K": K, "N": int(a["N"]),
           "mesh": str(dev_run[0].mesh.devices.ravel()[0].platform)
           + f"x{dev_run[0].D}", "cpu_mesh": f"cpux{d_cpu}"}
    for key in ("loss", "dual_residual", "primal_residual"):
        out[key] = a[key]
        out[key + "_cpu"] = b[key]
        out[key + "_rel"] = _rel(a[key], b[key])
        check(out[key + "_rel"] <= rtol,
              f"parity: {key} {a[key]!r} vs CPU {b[key]!r} "
              f"(rel {out[key + '_rel']:.2e} > {rtol})")
    return out, dev_run


# ----------------------------------------------------------------------
# kernels: compiled Pallas vs XLA at driver shapes
# ----------------------------------------------------------------------
def _largest_admitted(fits, P: int, step: int = 512, cap: int = 1 << 16):
    """Largest D the fit-gate admits at width P, over multiples of
    ``step`` (already padded sizes, like P = 256)."""
    D = 0
    while D + step <= cap and fits(D + step, P):
        D += step
    return D


def phase_kernels(*, impl: str = "pallas", N: int = BIG_BLOCK_N,
                  chunk: int = 256, K: int = 8, D: int = 1,
                  infonce_shapes=None, cpc_shape=(128 * 32, 9)) -> dict:
    """Each of the five Pallas entry points with the impl forced to
    ``impl`` against the forced ``"xla"`` path, then what auto-dispatch
    resolves to at these shapes and at the CPC reference shape
    (batch 128 x Rc 32 rows; 9 patch positions on the synthetic cube)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from federated_pytorch_test_tpu.ops import comm_kernels, infonce
    from federated_pytorch_test_tpu.ops import topk_select

    rng = np.random.default_rng(0)
    out: dict = {"impl": impl}

    def both(force, fn, *args):
        res = []
        for which in ("xla", impl):
            with force(which):
                # a fresh lambda per impl: jit caches on the function
                # object and does not see the forced impl
                res.append(jax.block_until_ready(
                    jax.jit(lambda *a: fn(*a))(*args)))
        return res

    # --- comm kernels at [N/chunk, chunk] and [K, N/D] -----------------
    c = -(-N // chunk)
    vv = jnp.asarray(rng.normal(size=(c, chunk)).astype(np.float32)
                     * rng.lognormal(size=(c, 1)).astype(np.float32))
    force = comm_kernels.force_comm_kernels_impl
    (qx, sx), (qp, sp) = both(
        force, lambda v: comm_kernels.quantize_chunks(v, 127), vv)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sx), rtol=1e-6)
    dq = np.abs(np.asarray(qp, np.int32) - np.asarray(qx, np.int32))
    # hardware contract (PARITY.md): allclose, not bitwise — a scale that
    # differs in the last place moves a value on a rounding boundary by
    # one grid step
    check(dq.max() <= 1 and dq.mean() < 1e-3,
          f"quantize_chunks: max |dq| {dq.max()}, mismatch {dq.mean():.2e}")
    out["quantize_chunks"] = {"shape": [c, chunk], "max_dq": int(dq.max()),
                              "mismatch_frac": float(dq.mean())}

    acc = jnp.asarray(rng.normal(size=(c, chunk)).astype(np.float32))
    ax, ap = both(force, comm_kernels.dequant_add, acc, qx, sx)
    np.testing.assert_allclose(np.asarray(ap), np.asarray(ax),
                               rtol=1e-6, atol=1e-6)
    out["dequant_add"] = {"shape": [c, chunk]}

    n_seg = -(-N // D)
    stack = jnp.asarray(rng.normal(size=(K, n_seg)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        gx, gp = both(force, comm_kernels.gram_matrix, stack)
    ref = np.asarray(stack, np.float64)
    ref = ref @ ref.T
    scale = float(np.abs(ref).max())
    err = {"xla": float(np.abs(np.asarray(gx) - ref).max() / scale),
           impl: float(np.abs(np.asarray(gp) - ref).max() / scale)}
    check(err[impl] <= 1e-4,
          f"gram_matrix[{impl}] off the float64 Gram by {err[impl]:.2e} "
          f"of its scale (xla: {err['xla']:.2e})")
    out["gram_matrix"] = {"shape": [K, n_seg], "rel_err_vs_f64": err}

    # --- InfoNCE forward + backward -----------------------------------
    if infonce_shapes is None:
        # (D, P, with backward): the bench shape, then the largest D each
        # fit-gate admits at P = 256 — the forward gate's shape runs the
        # forward alone (its backward is past the backward gate)
        infonce_shapes = [
            (512, 256, True),
            (_largest_admitted(infonce._pallas_fits, 256), 256, False),
            (_largest_admitted(infonce._pallas_bwd_fits, 256), 256, True)]
    out["infonce"] = []
    R, clients = 32, 4                 # the reference's Rc and its K
    for Dz, P, with_grad in infonce_shapes:
        # [B, P, 1, R] patches flatten to the [B*R, P] matrix the op
        # sees; vmapped over a client axis as the CPC engine calls it
        # (the extra grid step is what makes Mosaic pipeline the blocks)
        shape = (clients, Dz // R, P, 1, R)
        z = jnp.asarray(rng.normal(size=shape), jnp.float32)
        zh = jnp.asarray(rng.normal(size=shape), jnp.float32)
        fn = jax.vmap(
            jax.value_and_grad(infonce.info_nce_fused, argnums=(0, 1))
            if with_grad else infonce.info_nce_fused)
        with jax.default_matmul_precision("highest"):
            rx, rp = both(infonce.force_infonce_impl, fn, z, zh)
        (vx, gxs), (vp, gps) = (rx, rp) if with_grad else ((rx, ()), (rp, ()))
        np.testing.assert_allclose(np.asarray(vp), np.asarray(vx), rtol=1e-4)
        for a, b in zip(gps, gxs):
            b = np.asarray(b)
            # tests/test_ops.py _grad_tol: accelerator matmul rounding
            np.testing.assert_allclose(np.asarray(a), b, rtol=2e-3,
                                       atol=1e-5 * float(np.abs(b).max()))
        out["infonce"].append({"D": Dz, "P": P, "backward": with_grad,
                               "loss_rel": _rel(float(vp[0]), float(vx[0]))})

    # --- what auto-dispatch does with these shapes ---------------------
    plans = {"comm_kernels": comm_kernels.dispatch_plan(chunk, K),
             "topk": topk_select.dispatch_plan(N),
             "infonce_cpc_reference": infonce.dispatch_plan(*cpc_shape)}
    for Dz, P, _ in infonce_shapes:
        plans[f"infonce_{Dz}x{P}"] = infonce.dispatch_plan(Dz, P)
    for name, plan in plans.items():
        print(f"auto-dispatch {name}: {json.dumps(plan)}")
    out["dispatch"] = plans
    return out


# ----------------------------------------------------------------------
# compressed rounds: the kernels as the engine calls them
# ----------------------------------------------------------------------
COMPRESSED_SETTINGS = (
    ("q8+fused", dict(compress="q8", fused_collective=True)),
    ("topk", dict(compress="topk")),
    ("krum+chunked", dict(robust_agg="krum", robust_chunked=True)),
)


def phase_compressed(K: int, *, settings=COMPRESSED_SETTINGS, bf16=True,
                     **size_kw) -> dict:
    from federated_pytorch_test_tpu.parallel.mesh import client_mesh

    out = {}
    for name, kw in settings:
        trainer, _, history = block_round(client_mesh(), K, bf16=bf16,
                                          **kw, **size_kw)
        D, r = trainer.D, history[-1]
        out[name] = dict(_round_summary(history), D=D,
                         bytes_on_wire=int(r["bytes_on_wire"]))
        if kw.get("fused_collective"):
            # packed hops of the fused reduction: log2(D) butterfly steps
            # on a power-of-two mesh, D-1 ring steps otherwise, none on
            # one device (the kernels then never run)
            out[name].update(
                hops=0 if D == 1 else (int(math.log2(D)) if D & (D - 1) == 0
                                       else D - 1),
                bytes_fused=int(r["bytes_fused"]))
        print(f"compressed[{name}]: " + " ".join(
            f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in out[name].items()))
    return out


# ----------------------------------------------------------------------
# mesh: K/D rows per device; D-device round == 1-device round
# ----------------------------------------------------------------------
def phase_mesh(K: int, *, full_run=None, rtol: float = 1e-4,
               **size_kw) -> dict:
    """``full_run`` is a :func:`block_round` result on every device (the
    parity phase's); the same round runs again on ``num_devices=1``."""
    import jax
    import numpy as np

    from federated_pytorch_test_tpu.parallel.mesh import client_mesh

    if full_run is None:
        full_run = block_round(client_mesh(), K, precision="highest",
                               **size_kw)
    trainer, state, history = full_run
    D = trainer.D
    check(D == len(jax.devices()) > 1, f"mesh phase needs every device "
          f"of a multi-device host, got D={D}")
    rows = K // D
    sharded = {"params": state.params, "opt_state": state.opt_state,
               "device_data": trainer._dev_x}
    for name, tree in sharded.items():
        for leaf in jax.tree.leaves(tree):
            shards = leaf.addressable_shards
            check(len({s.device for s in shards}) == D
                  and all(s.data.shape[0] == rows for s in shards),
                  f"{name}: leaf {leaf.shape} is not {rows} client rows on "
                  f"each of {D} devices")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    if trainer.mesh.devices.ravel()[0].platform != "cpu":
        check(all(in_use), f"a device holds nothing: bytes_in_use={in_use}")

    one = block_round(client_mesh(1), K, precision="highest", **size_kw)
    a, b = _round_summary(history), _round_summary(one[2])
    out = {"K": K, "D": D, "rows_per_device": rows, "bytes_in_use": in_use}
    for key in ("loss", "dual_residual", "primal_residual"):
        out[key + "_rel"] = _rel(a[key], b[key])
        check(out[key + "_rel"] <= rtol,
              f"mesh: {key} on {D} devices {a[key]!r} vs 1 device "
              f"{b[key]!r} (rel {out[key + '_rel']:.2e} > {rtol})")
    # per-element: the two programs differ in vmap width (K/D vs K rows),
    # so gradients differ in the last place, and Adam turns a last-place
    # difference in a near-zero gradient into a visible one.  Hence two
    # bounds: almost no element may move at all, and none may move by
    # more than Adam can step (lr per minibatch, either sign).
    cfg = trainer.cfg
    bound = 2 * cfg.lr * cfg.Nadmm * cfg.Nepoch * trainer.data.steps
    moved = total = 0
    worst = 0.0
    for x, y in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(one[1].params)):
        x, y = np.asarray(x), np.asarray(y)
        diff = np.abs(x - y)
        moved += int(np.count_nonzero(diff > 1e-6 + 1e-4 * np.abs(y)))
        total += diff.size
        worst = max(worst, float(diff.max()))
    out.update(params_moved_frac=moved / total, params_max_abs_diff=worst)
    check(moved / total < 1e-4 and worst <= bound,
          f"mesh: {moved} of {total} parameters differ between {D} devices "
          f"and 1 (max |diff| {worst:.2e}, Adam bound {bound:.1e})")
    return out


# ----------------------------------------------------------------------
def _require_tpu():
    """Ask for the TPU by name before the first device query.  The
    sandbox exports JAX_PLATFORMS=cpu; inheriting it would turn the smoke
    into a CPU run that passes.  "cpu" rides second only so the parity
    phase can build its reference mesh — with an explicit platform list
    jax raises when the first entry cannot initialize."""
    import jax

    jax.config.update("jax_platforms", "tpu,cpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU backend: {e}", file=sys.stderr)
        sys.exit(2)
    if devices[0].platform != "tpu":
        print(f"chip_smoke: backend is not tpu: {devices}", file=sys.stderr)
        sys.exit(2)
    return devices


def run_phases(phases) -> tuple:
    """Run ``(name, fn)`` phases in order.  A phase that raises is
    recorded and the later ones still run — one chip call should show
    every failure — but its name lands in the returned ``failed`` list,
    and :func:`main` exits non-zero on a non-empty one."""
    import traceback

    results, failed = {}, []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            result, status = fn(), "ok"
        except Exception as e:           # noqa: BLE001 — phase boundary
            traceback.print_exc()
            failed.append(name)
            result, status = {"error": f"{type(e).__name__}: {e}"}, "FAILED"
        dt = time.perf_counter() - t0
        results[name] = dict(result, status=status, seconds=dt)
        print(f"phase {name}: {status} in {dt:.1f}s "
              f"{json.dumps(result, default=str)[:600]}", flush=True)
    return results, failed


def main() -> int:
    # the package first: next to nothing else of the repo this must fail
    # before anything reaches for the chip
    import federated_pytorch_test_tpu  # noqa: F401

    import jax
    import jaxlib
    from importlib.metadata import version

    from federated_pytorch_test_tpu.utils.compile_cache import (
        cache_stats,
        enable_persistent_compile_cache,
    )

    devices = _require_tpu()
    cache_dir = enable_persistent_compile_cache()
    D = len(devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": D}
    print(f"chip_smoke: jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={version('libtpu')} platform={device['platform']} "
          f"device_kind={device['kind']!r} device_count={D} "
          f"compile_cache={cache_dir} "
          f"(entries at start: {cache_stats()['entries']})", flush=True)

    phases = [("train", lambda: phase_train(8 * D))]
    if D > 1:
        # the mesh phase compares against the parity phase's run on every
        # device; a failed parity leaves None and mesh runs its own
        full_run = []

        def parity():
            result, run = phase_parity(2 * D)
            full_run.append(run)
            return result

        phases += [("parity", parity),
                   ("mesh", lambda: phase_mesh(
                       2 * D, full_run=full_run.pop() if full_run else None))]
    else:
        phases.append(("parity", lambda: phase_parity(2 * D)[0]))
    phases += [("kernels", lambda: phase_kernels(K=8 * D, D=D)),
               ("compressed", lambda: phase_compressed(8 * D, steps=8))]
    results, failed = run_phases(phases)
    if D == 1:
        print("phase mesh: needs a second device; not run on one chip")

    stats = cache_stats()
    print(f"chip_smoke: compile cache {stats['dir']} holds "
          f"{stats['entries']} entries ({stats['total_bytes']} bytes)")
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump({"ok": not failed, "device": device, "phases": results,
                   "compile_cache": stats}, f, indent=1, default=str)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
