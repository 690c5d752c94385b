"""Client-axis mesh construction.

Design (SURVEY.md section 7, decision 1): the K clients are a leading axis of
every stacked pytree, sharded over the mesh axis ``'clients'``.  When K exceeds
the device count each device holds a contiguous group of K/D clients (vmapped
locally inside ``shard_map``); when K equals the device count it is one client
per chip.  K must be a multiple of the device count used.

On hardware this axis lays onto ICI within a slice and DCN across slices
automatically via the standard device order of ``jax.sharding.Mesh``; tests run
the same code on a virtual 8-device CPU mesh (tests/conftest.py).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — re-exported: the engines import it from here
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

CLIENT_AXIS = "clients"

def client_mesh(num_devices: Optional[int] = None,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh over ``num_devices`` devices with axis ``'clients'``.

    An explicit ``num_devices`` must name a satisfiable size: zero,
    negative, or more-than-available values are user errors and raise
    (silent clamping/wrapping used to produce confusing downstream
    divisibility failures)."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if not 1 <= num_devices <= len(devices):
            raise ValueError(
                f"num_devices={num_devices} outside [1, {len(devices)}] "
                "available devices")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (CLIENT_AXIS,))


def usable_device_count(K: int, mesh_or_devices=None) -> int:
    """Largest device count D <= len(devices) with K % D == 0.

    Warns when the divisibility constraint collapses the mesh to far fewer
    devices than available (e.g. prime K=13 on 8 chips -> D=1): all clients
    then run vmapped on one chip, an ~n/D throughput cliff that is
    otherwise silent.
    """
    n = len(jax.devices() if mesh_or_devices is None else mesh_or_devices)
    d = min(n, K)
    while K % d:
        d -= 1
    if n > 1 and d <= n // 2 and K > d:
        import warnings
        warnings.warn(
            f"K={K} clients only divide onto {d} of {n} available devices; "
            f"choose K a multiple of the device count (or pass num_devices) "
            "to use the full mesh", stacklevel=2)
    return d


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (client) axis across the mesh."""
    return NamedSharding(mesh, P(CLIENT_AXIS))

def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_clients(tree, mesh: Mesh):
    """device_put every leaf with its leading axis sharded over 'clients'."""
    sh = client_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


# ---------------------------------------------------------------------------
# multi-host (DCN) support — SURVEY.md section 5 comm plan: the same
# collectives lower to ICI within a slice and DCN across slices; what
# multi-host additionally needs is (a) one jax.distributed runtime, (b)
# host->device staging that only materialises each process's addressable
# shards, and (c) host fetches that all-gather across processes.
# ---------------------------------------------------------------------------

def initialize_multihost() -> bool:
    """Join the multi-host JAX runtime when requested.

    Opt-in via ``FEDTPU_DISTRIBUTED=1`` (TPU pods auto-discover the
    coordinator; other platforms use the standard ``jax.distributed``
    env vars).  Call BEFORE any device query.  Returns True when running
    multi-process afterwards.  A no-op (False) when unset, so single-host
    behavior — every test, bench, and dry run — is unchanged.

    Explicit coordination hook (population-scale pods / CPU or GPU
    process launches, where there is no TPU metadata server to
    auto-discover from): ``FEDTPU_COORDINATOR=host:port`` plus
    ``FEDTPU_NUM_PROCESSES`` and ``FEDTPU_PROCESS_ID`` pass straight
    through to ``jax.distributed.initialize(coordinator_address=...,
    num_processes=..., process_id=...)``.  Set all three or none —
    a partial set is a config error and raises here, not as a hang at
    the first collective.
    """
    import os

    if os.environ.get("FEDTPU_DISTRIBUTED") != "1":
        # do NOT touch jax here: process_count() would initialize the
        # backend and defeat a later platform override (--no-use-tpu)
        return False
    if not jax.distributed.is_initialized():
        coord = os.environ.get("FEDTPU_COORDINATOR")
        nproc = os.environ.get("FEDTPU_NUM_PROCESSES")
        pid = os.environ.get("FEDTPU_PROCESS_ID")
        explicit = (coord, nproc, pid)
        if any(v is not None for v in explicit) \
                and not all(v is not None for v in explicit):
            raise ValueError(
                "FEDTPU_COORDINATOR, FEDTPU_NUM_PROCESSES and "
                "FEDTPU_PROCESS_ID must be set together (got "
                f"coordinator={coord!r}, num_processes={nproc!r}, "
                f"process_id={pid!r})")
        # genuine init failures (unreachable coordinator, ...) must raise:
        # a worker silently proceeding single-process while its peers
        # joined the global mesh hangs at the first collective instead
        if coord is not None:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=int(nproc),
                                       process_id=int(pid))
        else:
            jax.distributed.initialize()
    return jax.process_count() > 1


def _process_count() -> int:
    """Indirection over ``jax.process_count`` so tests can force the
    multi-process branches below without patching the jax module itself
    (``multihost_utils`` must keep seeing the true count)."""
    return jax.process_count()


# ---------------------------------------------------------------------------
# preemption-tolerant collectives — a peer process lost to preemption leaves
# every cross-process barrier/all-gather hung forever (jax.distributed's own
# heartbeat takes ~100s to notice, and the stock collectives have no
# deadline).  bounded_wait() converts that wedge into a typed error the
# restart supervisor can act on (reshape rung).  Default-off: timeout 0 runs
# the LITERAL unwrapped call — no helper thread, bit-identical, so the
# single-host and default multi-host paths are untouched.
# ---------------------------------------------------------------------------

class CollectiveTimeoutError(RuntimeError):
    """A multi-process collective or barrier exceeded its bounded wait —
    the signature of a peer lost to preemption.
    ``round_index`` (when known) lets the restart supervisor attribute
    the failure to a round without parsing the message."""

    def __init__(self, message: str, round_index: Optional[int] = None):
        super().__init__(message)
        self.round_index = round_index


def _env_barrier_timeout() -> float:
    try:
        return float(os.environ.get("FEDTPU_BARRIER_TIMEOUT", "0") or 0.0)
    except ValueError:
        return 0.0


#: active bound in seconds; <= 0 disables.  Seeded from the env so bare
#: scripts can arm it; engines override from cfg.barrier_timeout.
_BARRIER_TIMEOUT: float = _env_barrier_timeout()

#: (monotonic stamp, site name) of the last collective that COMPLETED —
#: the age of this record at timeout time says how long the process had
#: already been making progress-free.
_HEARTBEAT = {"stamp": None, "name": None}

#: elastic-collective state, touched from both the main thread and the
#: async checkpoint writer (its slot barriers route through
#: ``sync_global``), hence the lock:
#:   timeouts — process-lifetime count of bounded waits that expired
#:              (bench/obs counters)
#:   seq      — sequence number appended to coordination-service barrier
#:              ids; the service requires a fresh id per barrier
#:              instance, and SPMD guarantees every process issues the
#:              same barrier sequence, so the counter stays agreed
#:              across the job
_ELASTIC = {"timeouts": 0, "seq": 0}
_ELASTIC_LOCK = threading.Lock()


def configure_barrier_timeout(seconds: float) -> float:
    """Set the global bounded-wait deadline; returns the previous value.
    <= 0 disables (the literal unwrapped call path)."""
    global _BARRIER_TIMEOUT
    prev = _BARRIER_TIMEOUT
    _BARRIER_TIMEOUT = float(seconds)
    return prev


def barrier_timeout() -> float:
    return _BARRIER_TIMEOUT


def collective_timeout_count() -> int:
    return _ELASTIC["timeouts"]


def heartbeat(name: str) -> None:
    """Record that collective site ``name`` just completed."""
    _HEARTBEAT["stamp"] = time.monotonic()
    _HEARTBEAT["name"] = name


def last_heartbeat_age() -> Optional[float]:
    """Seconds since any collective last completed (None: none yet)."""
    stamp = _HEARTBEAT["stamp"]
    return None if stamp is None else time.monotonic() - stamp


def bounded_wait(fn: Callable, *, name: str,
                 timeout: Optional[float] = None):
    """Run blocking collective ``fn()`` with a deadline.

    With the effective timeout <= 0 (the default) this IS ``fn()`` — no
    thread, no wrapping.  Otherwise ``fn`` runs on a daemon thread and a
    ``join(timeout)`` bounds the wait: on expiry a
    :class:`CollectiveTimeoutError` carries the site name, the bound,
    and the last-heartbeat age.  The stuck daemon thread is abandoned —
    by construction the process is about to unwind to the restart
    supervisor (or die), and a hung XLA collective cannot be cancelled
    from python anyway.
    """
    t = _BARRIER_TIMEOUT if timeout is None else float(timeout)
    if t <= 0:
        out = fn()
        heartbeat(name)
        return out
    box: dict = {}

    def runner():
        try:
            box["value"] = fn()
        except BaseException as e:          # surface peer-side failures too
            box["error"] = e

    th = threading.Thread(target=runner, name=f"bounded-{name}", daemon=True)
    th.start()
    th.join(t)
    if th.is_alive():
        with _ELASTIC_LOCK:
            _ELASTIC["timeouts"] += 1
        age = last_heartbeat_age()
        last = ("no collective had completed yet" if age is None else
                f"last completed collective was {_HEARTBEAT['name']!r} "
                f"{age:.1f}s ago")
        raise CollectiveTimeoutError(
            f"collective {name!r} did not complete within {t:.1f}s "
            f"(process {jax.process_index()}/{_process_count()}; {last}) "
            "— peer lost to preemption?")
    if "error" in box:
        raise box["error"]
    heartbeat(name)
    return box.get("value")


def sync_global(tag: str, timeout: Optional[float] = None) -> None:
    """Cross-process barrier with the bounded wait applied.

    The shared entry point for every host-side barrier (checkpoint slot
    surgery, round fences).  No-op single-process, exactly like the raw
    ``sync_global_devices`` call it replaces.

    With a positive bound the barrier runs on the coordination service
    (``wait_at_barrier``): a pure-RPC rendezvous with a server-side
    deadline that works on every backend — the XLA barrier cannot be
    deadlined, and on the CPU backend it cannot even run cross-process.
    A missing peer (preemption) surfaces as the typed
    :class:`CollectiveTimeoutError` at the bound.  Timeout <= 0 keeps
    the stock XLA ``sync_global_devices`` path bit-for-bit.
    """
    if _process_count() == 1:
        return
    t = _BARRIER_TIMEOUT if timeout is None else float(timeout)
    if t > 0:
        from jax._src.distributed import global_state

        client = getattr(global_state, "client", None)
        if client is not None:
            with _ELASTIC_LOCK:
                _ELASTIC["seq"] += 1
                seq = _ELASTIC["seq"]
            name = f"sync:{tag}"
            try:
                client.wait_at_barrier(f"fedtpu:{tag}:{seq}",
                                       int(t * 1000))
            except Exception as e:
                with _ELASTIC_LOCK:
                    _ELASTIC["timeouts"] += 1
                age = last_heartbeat_age()
                last = ("no collective had completed yet" if age is None
                        else f"last completed collective was "
                             f"{_HEARTBEAT['name']!r} {age:.1f}s ago")
                raise CollectiveTimeoutError(
                    f"collective {name!r} did not complete within "
                    f"{t:.1f}s (process {jax.process_index()}/"
                    f"{_process_count()}; {last}) — peer lost to "
                    "preemption?") from e
            heartbeat(name)
            return

    def _sync():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)

    bounded_wait(_sync, name=f"sync:{tag}", timeout=t)


def stage_global(x, sharding: NamedSharding):
    """Host array -> global device array under ``sharding``.

    Single-process: a plain ``device_put``.  Multi-process: every process
    holds the SAME full array (the data pipelines are seed-deterministic,
    data/cifar10.py), and ``jax.make_array_from_callback`` materialises
    only this process's addressable shards — each host feeds its own
    slice of the client axis, nothing is sent over DCN at staging time.

    Callers left: what really starts on the host.  Epoch data, keys and
    the per-round masks (``_stage_epoch``, ``train/rounds.py``), the
    client norm stats (``cnorm``) and test set, checkpoint restore and
    the common init (through ``stage_tree_global``), a stateful
    compressor's fresh rows, and the small zeros of the independent and
    CPC loops.  The blockwise round loop's block switch is no longer
    one: its z / y / rho / x0 / yhat0 and top-k's scratch are made on
    the device (``BlockwiseFederatedTrainer._fresh_fn``).
    """
    if _process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def stage_tree_global(tree, sharding: NamedSharding):
    """``stage_global`` over every leaf (host/numpy-coerced first) — the
    shared checkpoint-restore staging path (engine restore, driver load).

    A leaf that is ALREADY a global jax.Array with non-addressable shards
    (orbax multi-host restore populates shardings from the checkpoint
    file) cannot be coerced through the host — ``np.asarray`` would try
    to fetch remote shards — so it is resharded on device instead.
    """
    def put(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return jax.device_put(x, sharding)
        return stage_global(np.asarray(x), sharding)

    return jax.tree.map(put, tree)


def fetch(x):
    """Device array -> host numpy, valid on every process.

    Single-process: ``np.asarray``.  Multi-process: client-sharded arrays
    have non-addressable shards, so all-gather across processes first.
    """
    if _process_count() == 1:
        return np.asarray(x)

    def _gather():
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    # cross-process all-gather: a preempted peer would hang this forever,
    # so it goes through the bounded wait (no-op at the default timeout 0)
    return bounded_wait(_gather, name="fetch:allgather")


def local_client_rows(mesh: Mesh, K: int) -> list:
    """Sorted client-axis rows whose shards live on THIS process's devices.

    The per-host data assignment: a host only needs to materialise (and a
    data pipeline only needs to build) the client rows it will feed —
    ``stage_client_rows`` turns that local slab into the global array.
    Single-process this is simply ``range(K)``.
    """
    sh = client_sharding(mesh)
    rows = set()
    for idx in sh.addressable_devices_indices_map((K,)).values():
        rows.update(range(*idx[0].indices(K)))
    return sorted(rows)


def stage_client_rows(x_local, sharding: NamedSharding):
    """Host array holding ONLY this process's client rows (leading axis in
    ``local_client_rows`` order) -> global device array under ``sharding``.

    Complements :func:`stage_global` (which wants the FULL array on every
    host): here each host hands over just its slab and nothing is copied
    or compared across DCN at staging time.  Single-process the local slab
    IS the full axis, so it is a plain ``device_put``.
    """
    if _process_count() == 1:
        return jax.device_put(x_local, sharding)
    return jax.make_array_from_process_local_data(sharding, x_local)
