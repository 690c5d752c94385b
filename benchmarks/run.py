#!/usr/bin/env python3
"""One run of one benchmark cell on the machine it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; the chip is touched once.  The cell is found by name
(``workloads/<cell>.json`` -> its configuration and traffic files), its
trainer is built by the module ``engines/<config's engine>.py``, and each
per-layer metric the cell lists is read by ``metrics/<metric>.py``.

Set-up (``setup_s``, process start to the window's opening): the
correctness check against the plain reference, then one whole untimed
pass of the cell's schedule, which compiles or loads every program the
window uses.  Window: whole passes through the engine's own ``run()``
until ``--seconds`` have passed.  The last line of stdout is the result
object; without a TPU, on a ``device_kind`` that ``lib/peaks.py`` does
not know, or with fewer devices than the cell's ``chips`` the run exits
non-zero and prints no result.

``--trace 0``: telemetry off, end-to-end metrics.  ``--trace 1``: the obs
recorder on for the whole window and ``jax.profiler`` on for one pass in
its middle; per-layer metrics, the device's busy time and ``breakdown``.

``--rehearse <json>`` lays tiny sizes over the cell's files and lets the
run go on without a TPU: the CPU rehearsal of ``benchmarks/tests``.  Its
result line says ``"platform": "cpu"``; it is never a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

EXIT_NO_DEVICE = 2


def fail(msg: str, code: int = 1):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr)
    sys.exit(code)


def device_or_exit(chips: int, rehearse: bool):
    """The devices this run measures on; exits where the contract says a
    run must not report."""
    import jax

    from benchmarks.lib import peaks

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not rehearse:
        if platform != "tpu":
            fail(f"no TPU: JAX's default backend is {platform!r}",
                 EXIT_NO_DEVICE)
        try:
            peaks.peaks_for(kind)
        except KeyError as e:
            fail(str(e.args[0]), EXIT_NO_DEVICE)
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX reports {len(devices)}",
             EXIT_NO_DEVICE)
    return devices[:chips], platform, kind


def finite(rec) -> bool:
    return all(math.isfinite(v) for v in rec.values()
               if isinstance(v, float))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", default=None, metavar="JSON")
    args = ap.parse_args(argv)

    from benchmarks.lib import cells, records as reclib
    from benchmarks.lib.window import Window

    cell = cells.load_cell(args.workload)
    if args.rehearse is not None:
        cell = cells.override(cell, json.loads(args.rehearse))

    # the compile cache: where JAX_COMPILATION_CACHE_DIR says, else the
    # repo's fixed path inside the checkout (utils/compile_cache.py)
    from federated_pytorch_test_tpu.utils.compile_cache import (
        cache_stats,
        enable_persistent_compile_cache,
    )

    cache_dir = enable_persistent_compile_cache()
    devices, platform, kind = device_or_exit(cell.chips,
                                             args.rehearse is not None)
    entries_before = cache_stats()["entries"]

    out_dir = os.path.join(BENCH, "out", cell.name)
    obs_dir = trace_dir = None
    if args.trace:
        obs_dir = os.path.join(out_dir, "obs")
        trace_dir = os.path.join(out_dir, "trace")
        for d in (obs_dir, trace_dir):     # the JSONL sink appends
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d)

    engine = importlib.import_module(
        f"benchmarks.engines.{cell.config['engine']}")
    t_built = time.perf_counter()
    session = engine.Session(cell, args.seed, obs_dir=obs_dir)
    check = session.check()
    print("check: " + json.dumps(check, default=float))

    window = Window(args.seconds, trace_dir)
    session.run(window)
    setup_s = window.t_open - T_START

    # ------------------------------------------------------------------
    recs = reclib.Records(
        warmup=window.warmup, passes=window.passes,
        samples_per_round=session.samples_per_round, chips=cell.chips,
        counters=dict(session.counters))
    warm, rounds = recs.warmup, recs.rounds()
    retraces0 = warm[-1].get("jit_retraces", 0)
    failed = sum(1 for r in rounds
                 if not finite(r) or "compile_seconds" in r
                 or r.get("jit_retraces", 0) > retraces0)
    loss = lambda recs: sum(r["loss"] for r in recs)
    last = window.passes[-1].records
    problems = list(check["problems"])
    if failed:
        problems.append(f"{failed} of {len(rounds)} rounds in the window "
                        "were non-finite, compiled or retraced")
    if not all(finite(r) for r in warm):
        problems.append("a round of the untimed pass is non-finite")
    if not loss(last) <= loss(warm) or (
            cell.config["engine"] == "classifier"
            and not loss(last) < loss(warm)):
        problems.append(f"loss of the last pass {loss(last)!r} is not below "
                        f"the first pass's {loss(warm)!r}")
    for p in problems:
        print("PROBLEM: " + p)

    # the peak of live buffers on the fullest chip.  What the runtime
    # reserves for the programs' scratch is a pool of its own (its peak
    # need not coincide, and tile padding fills much of it), so it is a
    # per-layer metric, `scratch_reserved_gib`, and no part of this
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    reserved = max(int(s.get("peak_bytes_reserved", 0)) for s in stats)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    sps = reclib.throughput(recs.timed(traced=False),
                            session.samples_per_pass, cell.chips)
    metrics = {}
    result = {"correct": not problems, "attempted": len(rounds),
              "failed": failed, "metrics": metrics, "device": device}
    if not args.trace:
        metrics["samples_per_s_chip"] = {"value": sps,
                                         "unit": "samples/s/chip"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        recs.counters.update(
            cache_entries_added=cache_stats()["entries"] - entries_before,
            peak_hbm_bytes=peak, scratch_reserved_bytes=reserved,
            traced_sps_chip=sps)
        view = reclib.trace_view(window, session.obs_path, kind)
        for name in cell.per_layer:
            reader = importlib.import_module(f"benchmarks.metrics.{name}")
            value = reader.read(recs, view, cell)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
        if view is not None:
            device["busy_s"] = view.busy_s()
            device["window_s"] = view.window_s
            result["breakdown"] = reclib.breakdown(view)
        elif args.rehearse is None:
            fail("the traced pass left no device trace to reduce")
    print(f"passes={len(window.passes)} pass_seconds="
          + json.dumps([round(p.seconds, 4) for p in window.passes])
          + f" setup_s={setup_s:.2f} (start {t_built - T_START:.2f}, check "
          f"{check['seconds']:.2f}, untimed pass "
          f"{setup_s - (t_built - T_START) - check['seconds']:.2f}) "
          f"cache_dir={cache_dir} memory={json.dumps(stats[0])}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
