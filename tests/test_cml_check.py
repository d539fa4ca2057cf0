"""cml-check static-analysis suite: known-bad fixtures must be caught,
the repo itself must be clean (modulo the checked-in baseline).

Run standalone with ``pytest -m analysis``; part of tier-1 (not slow).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from consensusml_tpu.analysis import (
    Finding,
    load_baseline,
    split_suppressed,
)
from consensusml_tpu.analysis import host_sync, locks, schedule
from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
from consensusml_tpu.topology import RingTopology, Shift

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "cml_check.py")


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# host-sync lint: known-bad snippets
# ---------------------------------------------------------------------------


def _lint(src: str):
    return host_sync.lint_source(textwrap.dedent(src), "fixture.py")


def test_sync_in_jitted_function_is_flagged():
    fs = _lint(
        """
        import jax

        @jax.jit
        def step(x):
            y = x + 1
            jax.block_until_ready(y)
            return y
        """
    )
    assert "sync-in-traced" in _rules(fs)


def test_numpy_in_scan_body_is_flagged():
    fs = _lint(
        """
        import jax
        import numpy as np

        def outer(xs):
            def body(carry, x):
                return carry + np.asarray(x), None
            return jax.lax.scan(body, 0.0, xs)
        """
    )
    assert "numpy-in-traced" in _rules(fs)


def test_time_in_shard_mapped_function_is_flagged():
    fs = _lint(
        """
        import time
        import jax

        def per_worker(x):
            t0 = time.time()
            return x * t0

        def build(mesh, P):
            return jax.shard_map(per_worker, mesh=mesh, in_specs=P, out_specs=P)
        """
    )
    assert "time-in-traced" in _rules(fs)


def test_branch_on_traced_param_is_flagged_but_static_forms_are_not():
    fs = _lint(
        """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(x, state, cfg):
            if x > 0:            # BAD: tracer truthiness
                x = x - 1
            if state is None:    # ok: presence check
                x = x + 1
            if cfg.h > 2:        # ok: attribute access = static config
                x = x * 2
            if len(x) > 1:       # ok: static shape info
                x = x + 2
            return x
        """
    )
    hits = [f for f in fs if f.rule == "branch-on-traced"]
    assert [f.detail for f in hits] == ["x"]


def test_item_in_vmapped_function_is_flagged():
    fs = _lint(
        """
        import jax

        def f(x):
            return x.item()

        g = jax.vmap(f)
        """
    )
    assert "item-in-traced" in _rules(fs)


def test_nested_and_called_functions_inherit_tracedness():
    fs = _lint(
        """
        import jax

        def helper(x):
            jax.device_get(x)   # traced via call from `step`
            return x

        @jax.jit
        def step(x):
            def inner(y):
                return y.tolist()   # traced via nesting
            return helper(x)
        """
    )
    rules = _rules(fs)
    assert "sync-in-traced" in rules and "item-in-traced" in rules


def test_host_side_sync_is_inventoried_not_traced_rule():
    fs = _lint(
        """
        import jax

        def save(state):
            return jax.device_get(state)
        """
    )
    assert _rules(fs) == ["host-sync"]
    assert fs[0].symbol == "save"


def test_clean_traced_code_has_no_findings():
    fs = _lint(
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, rng):
            y = jnp.where(x > 0, x, -x)
            if rng is None:
                return y
            return y + jax.random.normal(rng, y.shape)
        """
    )
    assert fs == []


def test_tree_map_is_not_mistaken_for_lax_map():
    fs = _lint(
        """
        import jax

        def place(batch):
            return jax.tree.map(lambda x: x if x.ndim else x, batch)
        """
    )
    assert fs == []


# ---------------------------------------------------------------------------
# lock-discipline lint
# ---------------------------------------------------------------------------


def _lint_locks(src: str):
    return locks.lint_source(textwrap.dedent(src), "fixture.py")


_LOCK_FIXTURE = """
    import threading
    from consensusml_tpu.analysis import guarded_by

    @guarded_by("_lock", "_value", "_count")
    class Shared:
        def __init__(self):
            self._lock = threading.Lock()
            self._value = 0        # ok: __init__ exempt
            self._count = 0

        def good(self):
            with self._lock:
                self._value += 1
                return self._count

        def bad_write(self):
            self._value += 1       # finding

        def bad_read(self):
            return self._value     # finding

        def bad_closure(self):
            with self._lock:
                def cb():
                    return self._count   # finding: closure escapes
                return cb

        def unannotated_ok(self):
            return id(self._lock)
"""


def test_lock_lint_flags_unguarded_access():
    fs = _lint_locks(_LOCK_FIXTURE)
    got = {(f.rule, f.symbol, f.detail) for f in fs}
    assert ("unguarded-write", "Shared.bad_write", "_value") in got
    assert ("unguarded-read", "Shared.bad_read", "_value") in got
    assert (
        "unguarded-read", "Shared.bad_closure.<locals>.cb", "_count"
    ) in got
    # nothing else: __init__ and with-lock accesses are clean
    assert len(fs) == 3


def test_lock_lint_flags_escaping_lambda_even_under_lock():
    """A lambda is a closure: written under the lock, handed to a
    thread, run without it — must be analyzed with an empty lock set
    exactly like a nested def."""
    fs = _lint_locks(
        """
        from consensusml_tpu.analysis import guarded_by

        @guarded_by("_lock", "_value")
        class Shared:
            def leak(self, spawn):
                with self._lock:
                    return spawn(target=lambda: self._value + 1)
        """
    )
    assert [(f.rule, f.symbol) for f in fs] == [
        ("unguarded-read", "Shared.leak.<locals>.<lambda>")
    ]


def test_lock_lint_ignores_classes_without_annotation():
    fs = _lint_locks(
        """
        class Plain:
            def touch(self):
                self._value = 1
        """
    )
    assert fs == []


def test_guarded_by_records_contract_at_runtime():
    from consensusml_tpu.analysis import guarded_by

    @guarded_by("_lock", "_a")
    @guarded_by("_other", "_b")
    class C:
        pass

    assert C.__guarded_by__ == {"_a": "_lock", "_b": "_other"}


def test_repo_threaded_modules_are_annotated_and_clean():
    """The threaded host-side modules carry @guarded_by and pass the
    lint — including the ISSUE 14 additions (hot-swap watcher, serve
    front-end, metrics HTTP server)."""
    for rel in (
        "consensusml_tpu/obs/metrics.py",
        "consensusml_tpu/obs/httpd.py",
        "consensusml_tpu/data/prefetch.py",
        "consensusml_tpu/native/__init__.py",
        "consensusml_tpu/utils/watchdog.py",
        "consensusml_tpu/serve/pool/hotswap.py",
        "consensusml_tpu/serve/server.py",
    ):
        path = os.path.join(REPO, rel)
        fs = locks.lint_file(path, REPO)
        assert fs == [], f"{rel}: {[f.render() for f in fs]}"
        src = open(path).read()
        assert "guarded_by(" in src, f"{rel} lost its annotations"


def test_bare_acquire_is_flagged():
    """ISSUE 14 satellite: the blind spot the old module docstring
    admitted — a bare acquire/release pair on a class's lock attr is now
    a finding (the in-tree occurrence in obs/httpd.py was converted to a
    with-guarded flag)."""
    fs = _lint_locks(
        """
        import threading

        class S:  # note: bare-acquire needs no @guarded_by annotation
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                self._lock.acquire()
                try:
                    return 1
                finally:
                    self._lock.release()

            def try_bad(self):
                if not self._lock.acquire(blocking=False):
                    return None
                self._lock.release()
        """
    )
    assert _rules(fs) == ["bare-acquire"]
    assert {f.symbol for f in fs} == {"S.bad", "S.try_bad"}
    # both calls in one method share one finding id (baseline granularity)
    assert len({f.id for f in fs if f.symbol == "S.bad"}) == 1


def test_guarded_escape_rules():
    """Escape analysis: returning/yielding a bare reference to a guarded
    MUTABLE leaks it out of the lock; copies, scalars and the ownership-
    transfer pattern stay clean."""
    fs = _lint_locks(
        """
        import threading
        from collections import deque
        from consensusml_tpu.analysis import guarded_by

        @guarded_by("_lock", "_items", "_ring", "_n")
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []
                self._ring = deque(maxlen=8)
                self._n = 0

            def leak(self):
                with self._lock:
                    return self._items          # finding

            def leak_gen(self):
                with self._lock:
                    yield self._ring            # finding

            def leak_alias(self):
                with self._lock:
                    out = self._items           # alias under lock
                return out                      # finding

            def ok_copy(self):
                with self._lock:
                    return list(self._items)

            def ok_transfer(self):
                with self._lock:
                    out, self._items = self._items, []
                return out

            def ok_scalar(self):
                with self._lock:
                    return self._n
        """
    )
    got = {(f.rule, f.symbol) for f in fs}
    assert got == {
        ("guarded-escape", "S.leak"),
        ("guarded-escape", "S.leak_gen"),
        ("guarded-alias-escape", "S.leak_alias"),
    }


def test_alias_rebound_to_copy_is_not_an_escape():
    """`x = self._items` under the lock then `x = list(x)` before the
    return — the very fix the escape rule recommends — is clean."""
    fs = _lint_locks(
        """
        import threading
        from consensusml_tpu.analysis import guarded_by

        @guarded_by("_lock", "_items")
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def snapshot(self):
                with self._lock:
                    out = self._items
                out = list(out)
                return out
        """
    )
    assert fs == [], [f.render() for f in fs]


# ---------------------------------------------------------------------------
# threads pass: spawn/handler inventory (ISSUE 14)
# ---------------------------------------------------------------------------


def _threads_run(tmp_path, code: str, doc: str):
    from consensusml_tpu.analysis import threads

    src = tmp_path / "mod.py"
    src.write_text(textwrap.dedent(code))
    docp = tmp_path / "threads.md"
    docp.write_text(textwrap.dedent(doc))
    return threads.run(
        str(tmp_path), py_files=[str(src)], doc_path=str(docp),
        report_stale=True,
    )


def test_unregistered_thread_is_flagged(tmp_path):
    """The acceptance bad fixture: a thread the inventory does not list
    is a finding; a documented one is clean."""
    fs = _threads_run(
        tmp_path,
        """
        import threading

        class W:
            def start(self):
                t = threading.Thread(
                    target=self._run, name="known-worker", daemon=True
                )
                u = threading.Thread(target=self._sneak, daemon=True)
                t.start(); u.start()
        """,
        "| `mod.py:W.start:known-worker` | yes | joined | documented |\n",
    )
    assert _rules(fs) == ["undocumented-thread"]
    (f,) = fs
    assert f.detail == "self._sneak" and f.symbol == "W.start"


def test_unregistered_handler_and_stale_doc_row(tmp_path):
    fs = _threads_run(
        tmp_path,
        """
        import signal

        def arm():
            signal.signal(signal.SIGTERM, lambda s, f: None)
        """,
        "| `mod.py:gone_fn:SIGUSR1` | - | | a thread of the past |\n",
    )
    assert _rules(fs) == ["stale-thread-doc", "undocumented-handler"]
    assert {f.detail for f in fs} == {"SIGTERM", "mod.py:gone_fn:SIGUSR1"}


def test_daemon_mismatch_is_flagged(tmp_path):
    fs = _threads_run(
        tmp_path,
        """
        import threading

        def spawn():
            threading.Thread(target=spin, name="w", daemon=False).start()
        """,
        "| `mod.py:spawn:w` | yes | joined | drifted |\n",
    )
    assert _rules(fs) == ["daemon-mismatch"]


def test_thread_spawner_with_undeclared_lock_contract_is_flagged(tmp_path):
    """A class that spawns a thread and owns a Lock but carries no
    @guarded_by: the sharing is real, the contract is invisible."""
    fs = _threads_run(
        tmp_path,
        """
        import threading

        class Undeclared:
            def __init__(self):
                self._lock = threading.Lock()
                self._t = threading.Thread(
                    target=self._run, name="undeclared", daemon=True
                )

        class Declared:
            pass
        """,
        "| `mod.py:Undeclared.__init__:undeclared` | yes | joined | ok |\n",
    )
    assert _rules(fs) == ["unannotated-thread-state"]
    assert fs[0].detail == "_lock"


def test_repo_thread_inventory_is_complete():
    """Acceptance: every thread/handler in the package + entry points is
    documented in docs/threads.md, no stale rows, no undeclared lock
    contracts — with NO baseline help."""
    from consensusml_tpu.analysis import threads

    fs = threads.check_repo(REPO)
    assert fs == [], [f.render() for f in fs]


# ---------------------------------------------------------------------------
# lockorder pass: static deadlock detection (ISSUE 14)
# ---------------------------------------------------------------------------


_ABBA_FIXTURE = """
    import threading

    class Registry:
        def __init__(self):
            self._lock = threading.Lock()
            self._w = Watcher()

        def poke(self):
            with self._lock:
                pass

        def scrape(self):
            with self._lock:
                self._w.take()      # holds Registry._lock -> Watcher._lock

    class Watcher:
        def __init__(self):
            self._lock = threading.Lock()
            self._reg = Registry()

        def take(self):
            with self._lock:
                pass

        def publish(self):
            with self._lock:
                self._reg.poke()    # holds Watcher._lock -> Registry._lock
"""


def test_abba_two_class_deadlock_is_detected_statically():
    """The acceptance bad fixture: opposite-order acquisition across two
    classes, composed through typed attributes and the call graph — a
    lock-cycle finding with no thread ever run."""
    from consensusml_tpu.analysis import lockorder

    model = lockorder.analyze_sources(
        [("fx.py", textwrap.dedent(_ABBA_FIXTURE))]
    )
    assert ("Registry._lock", "Watcher._lock") in model.edges
    assert ("Watcher._lock", "Registry._lock") in model.edges
    fs = model.findings()
    assert _rules(fs) == ["lock-cycle"]
    # canonical, line-number-free cycle detail => stable baseline id
    assert fs[0].detail == "Registry._lock->Watcher._lock->Registry._lock"
    assert fs[0].id == (
        "lockorder:lock-cycle:fx.py:<graph>:"
        "Registry._lock->Watcher._lock->Registry._lock"
    )


def test_branchy_scc_still_yields_a_witness_cycle():
    """A cycle inside a branchy SCC (where a greedy min-successor walk
    dead-ends) must still produce a lock-cycle finding, not an internal
    error: edges A->B, B->C, B->D, C->B, D->A."""
    from consensusml_tpu.analysis import lockorder

    model = lockorder.LockModel()
    for a, b in [("A", "B"), ("B", "C"), ("B", "D"), ("C", "B"),
                 ("D", "A")]:
        model.add_edge(a, b, "fx.py", 1, f"{a}->{b}")
    fs = model.findings()
    assert _rules(fs) == ["lock-cycle"], [f.render() for f in fs]
    # the witness is a real cycle through the graph's edges
    cyc = fs[0].detail.split("->")
    assert cyc[0] == cyc[-1]
    for x, y in zip(cyc, cyc[1:]):
        assert (x, y) in model.edges, (x, y)


def test_plain_lock_self_reentry_is_a_deadlock():
    from consensusml_tpu.analysis import lockorder

    model = lockorder.analyze_sources(
        [(
            "fx.py",
            textwrap.dedent(
                """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def outer(self):
                        with self._lock:
                            self.inner()

                    def inner(self):
                        with self._lock:
                            pass
                """
            ),
        )]
    )
    assert _rules(model.findings()) == ["self-deadlock"]


def test_rlock_reentry_is_exempt_self_loop():
    """The obs/requests.py idiom: _finish_locked re-enters the RLock the
    caller already holds — modeled as a re-entry, not a deadlock."""
    from consensusml_tpu.analysis import lockorder

    model = lockorder.analyze_sources(
        [(
            "fx.py",
            textwrap.dedent(
                """
                import threading

                class R:
                    def __init__(self):
                        self._lock = threading.RLock()

                    def finish(self):
                        with self._lock:
                            self._finish_locked()

                    def _finish_locked(self):
                        with self._lock:
                            pass
                """
            ),
        )]
    )
    assert model.findings() == []
    assert "R._lock" in model.reentries


def test_repo_lock_graph_is_acyclic_and_leaf_disciplined():
    """Acceptance: the package lock graph has NO cross-lock edges (every
    lock is leaf-level — nothing acquires one lock while holding
    another) and the only nesting is the request registry's documented
    RLock re-entry. A future edge is fine; a cycle never is."""
    from consensusml_tpu.analysis import lockorder

    model = lockorder.static_model(REPO)
    assert model.findings() == [], [
        f.render() for f in model.findings()
    ]
    assert model.edges == {}, sorted(model.edges)
    assert "RequestTraceRegistry._lock" in model.reentries


# ---------------------------------------------------------------------------
# schedule verifier
# ---------------------------------------------------------------------------

LEAVES = [((64, 8), "float32"), ((32,), "bfloat16"), ((513,), "float32")]


@pytest.mark.parametrize("bucket_bytes", [0, 4 * 2**20])
@pytest.mark.parametrize("name", sorted(schedule.builtin_topologies(8)))
def test_every_topology_schedule_verifies(name, bucket_bytes):
    """Satellite: every shipped topology x bucket_bytes in {0 (per-leaf),
    4MiB} materializes a deadlock-free, bijective schedule — exact and
    (static graphs) compressed."""
    from consensusml_tpu.compress import topk_int8_compressor

    topo = schedule.builtin_topologies(8)[name]
    bb = bucket_bytes or None  # 0 == per-leaf wire (GossipConfig contract)
    engines = [ConsensusEngine(GossipConfig(topology=topo, bucket_bytes=bb))]
    if not topo.is_time_varying:
        engines.append(
            ConsensusEngine(
                GossipConfig(
                    topology=topo,
                    compressor=topk_int8_compressor(
                        ratio=0.1, chunk=128, impl="jnp"
                    ),
                    gamma=0.5,
                    bucket_bytes=bb,
                )
            )
        )
    for eng in engines:
        fs = schedule.verify_engine(eng, LEAVES, source=f"test:{name}")
        assert fs == [], [f.render() for f in fs]


class _AsymmetricRing(RingTopology):
    """Deliberately broken: rank 0 gossips with different offsets than
    everyone else — the static form of a rank-divergent ppermute."""

    def rank_shifts(self, rank):
        if rank == 0:
            return (Shift(0, +3, 1.0 / 3), Shift(0, -1, 1.0 / 3))
        return self.shifts


def test_asymmetric_topology_is_reported_as_deadlock_statically():
    """The acceptance fixture: no mesh, no collective, no device — the
    deadlock is proven from the materialized schedules alone."""
    eng = ConsensusEngine(GossipConfig(topology=_AsymmetricRing(8)))
    fs = schedule.verify_engine(eng, LEAVES, source="test:asym")
    rules = _rules(fs)
    assert "deadlock-endpoint-mismatch" in rules
    # and the lint names both wedged endpoints of the first bad transfer
    details = {f.detail for f in fs if f.rule == "deadlock-endpoint-mismatch"}
    assert any(d.startswith("pos0:r0->") for d in details)


def test_rank_dependent_collective_count_is_a_deadlock():
    class ExtraShift(RingTopology):
        def rank_shifts(self, rank):
            if rank == 3:
                return self.shifts + (Shift(0, +2, 0.0),)
            return self.shifts

    eng = ConsensusEngine(GossipConfig(topology=ExtraShift(8)))
    fs = schedule.verify_engine(eng, LEAVES, source="test:count")
    assert _rules(fs) == ["deadlock-op-count"]


def test_non_bijective_perm_is_flagged():
    ops = [
        [
            schedule.RankOp(
                "ppermute", "workers", "leaf0", (8,), "float32",
                send_to=0 if r < 2 else r, recv_from=(r + 1) % 4,
            )
        ]
        for r in range(4)
    ]
    fs = schedule.verify_schedules(ops, source="test:nonbij", topology=None)
    assert "perm-not-bijective" in _rules(fs)


def test_payload_mismatch_across_ranks_is_flagged():
    mk = lambda dtype: [
        schedule.RankOp(
            "ppermute", "workers", "leaf0", (8,), dtype,
            send_to=(r + 1) % 4, recv_from=(r - 1) % 4,
        )
        for r in range(4)
    ]
    ops = [[op] for op in mk("float32")]
    ops[2] = [mk("bfloat16")[2]]  # rank 2 ships a different dtype
    fs = schedule.verify_schedules(ops, source="test:dtype", topology=None)
    assert "deadlock-op-mismatch" in _rules(fs)


def test_schedule_matches_engine_bucketing():
    """The materializer uses the engine's own plan: shrinking
    bucket_bytes must grow the per-shift op count accordingly."""
    topo = RingTopology(4)
    leaves = [((4096,), "float32"), ((4096,), "float32")]
    ops_for = lambda bb: len(
        schedule.materialize_schedules(
            ConsensusEngine(
                GossipConfig(topology=topo, bucket_bytes=bb)
            ),
            leaves,
        )[0]
    )
    assert ops_for(1 << 20) == 2  # one bucket x two shifts
    assert ops_for(8 * 1024) == 4  # two buckets x two shifts
    assert ops_for(None) == 4  # per-leaf x two shifts


# ---------------------------------------------------------------------------
# jaxpr contracts
# ---------------------------------------------------------------------------


def test_jaxpr_contracts_mnist_and_gpt2_clean():
    from consensusml_tpu.analysis import jaxpr_contracts

    for name in ("mnist_mlp", "gpt2_topk"):
        fs = jaxpr_contracts.check_config(name)
        assert fs == [], [f.render() for f in fs]


def test_jaxpr_decode_contracts_run_on_lm_configs_only():
    """The serving decode step carries its own contracts (no host
    callbacks, no f64, zero step-over-step recompiles) on the causal-LM
    configs; non-LM configs have no decode path and are skipped."""
    from consensusml_tpu import configs
    from consensusml_tpu.analysis.jaxpr_contracts import _check_decode_jaxpr

    for name in ("gpt2_topk", "llama_lora"):
        fs = _check_decode_jaxpr(name, configs.build(name))
        assert fs == [], [f.render() for f in fs]
    assert _check_decode_jaxpr("mnist_mlp", configs.build("mnist_mlp")) == []


def test_fused_wire_contract_is_clean():
    """ISSUE 9 CI satellite: the fused one-pass wire traces exactly one
    pallas_call per bucket per kernel stage (encode+decode on ppermute
    topologies, encode-only on psum) and its traced ppermute count still
    matches the schedule verifier's model."""
    from consensusml_tpu.analysis import jaxpr_contracts

    fs = jaxpr_contracts.check_fused_wire()
    assert fs == [], [f.render() for f in fs]


def test_fused_wire_contract_catches_unfused_fallback():
    """The fused-active rule fires when the fused wire silently falls
    back: the kernel-count rule fires when the traced program's
    pallas_call count drifts from the per-bucket contract (simulated
    here by lying to the checker about the expected count via a codec
    that never fuses — the fused-active finding is the canary)."""
    import consensusml_tpu.compress as C
    import consensusml_tpu.analysis.jaxpr_contracts as jc

    # a codec class whose instances refuse to fuse: auto-mode engines
    # silently keep the two-step path, which the contract must flag
    class NoFuse(C.PallasInt8Compressor):
        def fused_wire(self):
            return None

    real = C.PallasInt8Compressor
    C.PallasInt8Compressor = NoFuse
    try:
        fs = jc.check_fused_wire()
    finally:
        C.PallasInt8Compressor = real
    assert "fused-active" in _rules(fs), [f.render() for f in fs]


def test_jaxpr_callback_detector_sees_callbacks():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from consensusml_tpu.analysis.jaxpr_contracts import count_primitives

    def bad(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            x,
        )
        return jnp.sum(y)

    counts = count_primitives(jax.make_jaxpr(bad)(jnp.ones((4,))))
    assert any("callback" in k for k in counts), counts


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_suppression_and_stale_reporting(tmp_path):
    f1 = Finding("host-sync", "host-sync", "a.py", "f", "device_get", "m", 1)
    f2 = Finding("host-sync", "host-sync", "b.py", "g", "device_get", "m", 2)
    bl = tmp_path / "baseline"
    bl.write_text(
        f"# comment\n{f1.id}  # inline comment\nhost-sync:gone:entry:x:y\n"
    )
    active, suppressed, stale = split_suppressed(
        [f1, f2], load_baseline(str(bl))
    )
    assert [f.id for f in active] == [f2.id]
    assert [f.id for f in suppressed] == [f1.id]
    assert stale == ["host-sync:gone:entry:x:y"]


def test_finding_id_is_line_number_stable():
    a = Finding("locks", "unguarded-read", "m.py", "C.f", "_x", "msg", 10)
    b = Finding("locks", "unguarded-read", "m.py", "C.f", "_x", "msg", 99)
    assert a.id == b.id


# ---------------------------------------------------------------------------
# docs-drift pass: code families vs docs/observability.md
# ---------------------------------------------------------------------------


def _drift(tmp_path, code: str, doc: str):
    from consensusml_tpu.analysis import docs_drift

    src = tmp_path / "mod.py"
    src.write_text(textwrap.dedent(code))
    docp = tmp_path / "observability.md"
    docp.write_text(textwrap.dedent(doc))
    return docs_drift.run(
        str(tmp_path), py_files=[str(src)], doc_path=str(docp)
    )


def test_docs_drift_undocumented_metric_is_flagged(tmp_path):
    fs = _drift(
        tmp_path,
        """
        def f(reg):
            reg.counter("consensusml_widget_total", "widgets")
            reg.gauge("consensusml_depth", "documented one")
        """,
        "| `consensusml_depth` | gauge | documented |\n",
    )
    assert _rules(fs) == ["undocumented-metric"]
    (f,) = fs
    assert f.detail == "consensusml_widget_total" and f.symbol == "f"


def test_docs_drift_stale_doc_entry_is_flagged(tmp_path):
    fs = _drift(
        tmp_path,
        """
        def f(reg):
            reg.counter("consensusml_widget_total")
        """,
        "`consensusml_widget_total` and `consensusml_gone_total`\n",
    )
    assert _rules(fs) == ["stale-doc-metric"]
    assert fs[0].detail == "consensusml_gone_total"


def test_docs_drift_dynamic_prefix_exempts_doc_entries(tmp_path):
    # f-string-composed families: the literal prefix marks the namespace
    # as dynamically emitted, so doc rows under it are not stale — but
    # the bare consensusml_ prefix must NOT blanket-exempt everything
    fs = _drift(
        tmp_path,
        """
        def f(reg, kind):
            reg.counter(f"consensusml_swarm_{kind}_total")
        """,
        "`consensusml_swarm_join_total` but also `consensusml_vanished`\n",
    )
    assert _rules(fs) == ["stale-doc-metric"]
    assert fs[0].detail == "consensusml_vanished"


def test_docs_drift_repo_is_clean():
    """The repo's metric schema agrees with docs/observability.md —
    modulo the baselined dynamically-composed families (engine
    telemetry gauges, MetricsLogger per-field gauges)."""
    from consensusml_tpu.analysis import docs_drift

    findings = docs_drift.check_repo(REPO)
    baseline = load_baseline(os.path.join(REPO, ".cml-check-baseline"))
    active, suppressed, _stale = split_suppressed(findings, baseline)
    assert active == []
    # every suppression is a stale-doc entry for a dynamic family, never
    # an undocumented emission
    assert all(f.rule == "stale-doc-metric" for f in suppressed)


# ---------------------------------------------------------------------------
# the CLI gate (acceptance criteria)
# ---------------------------------------------------------------------------


def _run_cli(*args, timeout=240):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI sets its own device count
    return subprocess.run(
        [sys.executable, CLI, *args],
        capture_output=True, text=True, cwd=REPO, timeout=timeout, env=env,
    )


def test_cli_all_exits_zero_on_repo():
    """`python tools/cml_check.py --all` is the tier-1 gate: the repo is
    clean under the checked-in baseline, machine-readably."""
    res = _run_cli("--all", "--json", "-")
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["ok"] is True
    assert doc["findings"] == []
    assert doc["counts"]["suppressed"] >= 1  # the intentional-sync inventory
    assert doc["counts"]["stale"] == 0, doc["stale_baseline"]
    assert set(doc["passes"]) == {
        "host-sync", "locks", "threads", "lockorder", "docs-drift",
        "lifecycle", "model", "schedule", "jaxpr",
    }
    # per-pass wall time rides the JSON; the AST passes hold their
    # absolute budget (<2 s each: this assertion is the gate; the
    # exhaustive model checker gets 30 s)
    secs = doc["pass_seconds"]
    for name in ("host-sync", "locks", "threads", "lockorder", "docs-drift",
                 "lifecycle"):
        assert secs[name] < 2.0, (name, secs)
    assert secs["model"] < 30.0, secs


def test_cli_exits_nonzero_on_threads_bad_fixture(tmp_path):
    """An undocumented thread in a --paths-restricted tree fails the
    gate without dragging the repo inventory's rows in as stale."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        textwrap.dedent(
            """
            import threading

            def spawn():
                threading.Thread(target=spawn, daemon=True).start()
            """
        )
    )
    res = _run_cli(
        "--threads", "--paths", str(bad), "--json", "-", timeout=120,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert [f["rule"] for f in doc["findings"]] == ["undocumented-thread"]
    assert doc["stale_baseline"] == []


def test_cli_path_restricted_run_does_not_report_foreign_stale(tmp_path):
    """`--paths` narrowing must not flag baseline entries for files the
    run never scanned as stale (a developer would prune live
    suppressions)."""
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    res = _run_cli(
        "--host-sync", "--paths", str(clean), "--json", "-", timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["stale_baseline"] == []


def test_cli_exits_nonzero_on_lockorder_bad_fixture(tmp_path):
    """The ABBA tree fails the gate through the CLI too."""
    bad = tmp_path / "abba.py"
    bad.write_text(textwrap.dedent(_ABBA_FIXTURE))
    res = _run_cli(
        "--lockorder", "--paths", str(tmp_path), "--baseline", "none",
        "--json", "-", timeout=120,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert any(f["rule"] == "lock-cycle" for f in doc["findings"])


def test_cli_exits_nonzero_on_bad_fixture(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        textwrap.dedent(
            """
            import jax

            @jax.jit
            def step(x):
                jax.block_until_ready(x)
                return x
            """
        )
    )
    res = _run_cli(
        "--host-sync", "--paths", str(bad), "--baseline", "none",
        "--json", "-", timeout=120,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert any(f["rule"] == "sync-in-traced" for f in doc["findings"])
