"""The tier-1 run's schedule under ``pytest -n <workers> --dist load``:
``conftest.deal_long_first`` against stand-in items and against xdist's
own ``LoadScheduling``, the tests that carry ``long``, and the files
whose cases share a build collected key by key."""

import collections
import importlib
import inspect
import os
import subprocess
import sys
import types

import pytest
from xdist.scheduler import LoadScheduling

import conftest
from conftest import deal_long_first
from test_scope_names import PROGRAMS

HERE = os.path.dirname(os.path.abspath(__file__))

W, N, N_LONG = 6, 1537, 13
CHUNK = max(2, N // W // 4)
#: the self-contained tests of a minute or more on the CPU (seconds of
#: one whole ``-n 6`` run of the suite)
LONG = {
    "test_xing_benchmark.py::test_rehearsal_of_the_cell": 238,
    "test_lm_benchmark.py::test_rehearsal_of_the_cell": 134,
    "test_glm_benchmark.py::test_rehearsal_of_the_cell": 128,
    "test_xing_benchmark.py::"
    "test_the_check_fails_a_float8_probe_at_the_small_size": 125,
    "test_zaya_benchmark.py::test_rehearsal_of_the_cell": 109,
    "test_xing4_0.py::"
    "test_model_through_the_stream_kernels_matches_the_jax_numpy_path": 108,
    "test_olmo_hybrid_benchmark.py::test_rehearsal_of_the_cell": 107,
    "test_qwen3_next.py::"
    "test_model_through_the_attention_kernels_matches_the_xla_path": 99,
    "test_scope_tree.py::test_the_readers_in_a_rehearsed_traced_run": 94,
    "test_golden_trajectories.py::TestCPCGolden::test_default_path": 80,
    "test_xing4_0.py::"
    "test_model_through_the_attention_kernels_matches_the_xla_path": 79,
    "test_zaya_benchmark.py::"
    "test_the_check_fails_a_float8_probe_at_the_small_size": 75,
    "test_obs.py::TestEngineEmission::test_cpc_records": 67,
    "test_olmo_hybrid_benchmark.py::"
    "test_the_check_fails_a_float8_probe_at_the_small_size": 67,
}


class Item:
    """What the dealing reads of a pytest item: its file and markers."""

    def __init__(self, path, name, seconds=None):
        self.path, self.nodeid = path, f"{path}::{name}"
        self.mark = None if seconds is None else \
            pytest.mark.long(seconds=seconds).mark

    def get_closest_marker(self, name):
        return self.mark if name == "long" else None


def collection():
    """``N`` items in files of 12, ``N_LONG`` of them long, spread over
    the second half of the order as the suite's late files hold them."""
    items = [Item(f"test_{i // 12:03d}.py", f"t{i}") for i in range(N)]
    for k in range(N_LONG):
        at = N // 2 + k * (N // 2 // N_LONG)
        items[at] = Item(items[at].path, f"long{k}", seconds=60 + 10 * k)
    return items


def chunks_of(order):
    return [order[k * CHUNK:(k + 1) * CHUNK] for k in range(W)]


def is_long(item):
    return item.get_closest_marker("long") is not None


# ----------------------------------------------------------------------
def test_long_items_are_dealt_over_the_first_chunks():
    items = collection()
    order = deal_long_first(items, W)
    assert collections.Counter(map(id, order)) == \
        collections.Counter(map(id, items))
    assert [it for it in order if not is_long(it)] == \
        [it for it in items if not is_long(it)]
    assert all(order.index(it) < W * CHUNK for it in items if is_long(it))
    per_chunk = [sum(map(is_long, chunk)) for chunk in chunks_of(order)]
    assert max(per_chunk) <= 3 and min(per_chunk) >= 2 and \
        sum(per_chunk) == N_LONG
    # longest first, round-robin: the longest opens the first chunk
    seconds = sorted((it.mark.kwargs["seconds"] for it in items
                      if is_long(it)), reverse=True)
    firsts = [next(it for it in chunk if is_long(it))
              for chunk in chunks_of(order)]
    assert [it.mark.kwargs["seconds"] for it in firsts] == seconds[:W]


def test_long_items_go_in_at_a_file_boundary():
    order = deal_long_first(collection(), W)
    for k, chunk in enumerate(chunks_of(order)):
        j = next(j for j, it in enumerate(chunk) if is_long(it))
        before = chunk[j - 1] if j else (order[k * CHUNK - 1] if k else None)
        after = next(it for it in chunk[j:] if not is_long(it))
        # the first chunk starts at the head; the others at the first
        # change of file inside them
        assert before is None or before.path != after.path, k
        assert all(a.path == b.path for a, b in zip(chunk[:j], chunk[1:j]))


def test_outside_xdist_the_order_is_unchanged():
    items = collection()
    kept = list(items)
    conftest.pytest_collection_modifyitems(types.SimpleNamespace(), items)
    assert items == kept
    plain = [Item("test_a.py", f"t{i}") for i in range(40)]
    assert deal_long_first(plain, W) == plain


class Node:
    """A stand-in for xdist's ``WorkerController``."""

    shutting_down = False

    def __init__(self, k):
        self.gateway = types.SimpleNamespace(id=f"gw{k}")
        self.batches = []

    def send_runtest_some(self, indices):
        self.batches.append(list(indices))

    def shutdown(self):
        self.shutting_down = True


def test_xdist_sends_each_worker_its_long_items_first():
    """The installed ``LoadScheduling`` hands each worker first the
    chunk ``deal_long_first`` filled for it, long items and all."""
    config = types.SimpleNamespace(
        getvalue=lambda name: [f"{W}*popen"] if name == "tx" else None,
        getoption=lambda name: None)
    sched = LoadScheduling(config)
    nodes = [Node(k) for k in range(W)]
    order = deal_long_first(collection(), W)
    ids = [it.nodeid for it in order]
    for node in nodes:
        sched.add_node(node)
    for node in nodes:
        sched.add_node_collection(node, ids)
    sched.schedule()
    for node, chunk in zip(nodes, chunks_of(order)):
        first = [ids[i] for i in node.batches[0]]
        assert first == [it.nodeid for it in chunk]
        assert 2 <= sum("::long" in i for i in first) <= 3


# ----------------------------------------------------------------------
def _test_object(nodeid):
    path, *names = nodeid.split("::")
    obj = importlib.import_module(path[:-len(".py")])
    for name in names:
        obj = getattr(obj, name)
    return obj


@pytest.mark.parametrize("nodeid", sorted(LONG))
def test_the_long_tests_carry_long_and_take_no_shared_fixture(nodeid):
    fn = _test_object(nodeid)
    marks = getattr(fn, "pytestmark", [])
    assert [m.kwargs for m in marks if m.name == "long"] == \
        [{"seconds": LONG[nodeid]}]
    # only parameters, and fixtures built for each test alone
    params = {name.strip() for m in marks if m.name == "parametrize"
              for name in m.args[0].split(",")}
    args = set(inspect.signature(fn).parameters) - {"self"}
    assert args <= params | {"tmp_path"}, args


def test_files_collect_the_cases_of_one_build_together():
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_scope_names.py",
         "tests/test_gated_delta_kernel.py", "--collect-only", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "no:xdist"],
        cwd=os.path.dirname(HERE), capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    ids = [line for line in out.stdout.split("\n") if "::" in line]
    which = {i: w for i in ids for w in PROGRAMS
             if i.startswith("tests/test_scope_names.py") and f"[{w}]" in i}
    runs = [which[i] for i in ids if i in which]
    assert len(runs) == 4 * len(PROGRAMS)
    # one contiguous run per program, after the kernel readers' test
    assert [w for j, w in enumerate(runs) if j == 0 or runs[j - 1] != w] \
        == list(PROGRAMS)
    readers = ids.index(
        "tests/test_scope_names.py::test_the_kernel_readers_names_still_occur")
    assert readers < min(ids.index(i) for i in which)
    keys = [i.split("[")[1].split("-", 1)[1] for i in ids
            if "test_checkpointed_gradient_is_todays[" in i]
    assert len(keys) == 20 and \
        sum(j == 0 or keys[j - 1] != k for j, k in enumerate(keys)) == 4
