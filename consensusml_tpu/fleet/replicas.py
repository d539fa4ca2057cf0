"""Replica lifecycle: spawn, readiness-gate, kill detection, restart.

A *replica* is one :class:`~consensusml_tpu.serve.server.ServeServer`
(engine + line-JSON front-end, optionally a metrics side-server). The
router and controller never talk to engines directly — they see replica
*handles*, all sharing one duck-typed surface:

- ``name`` / ``address`` / ``artifact`` — identity, the front-end's
  ``(host, port)`` (``None`` until ready), and the artifact directory
  the replica's hot-swap watcher polls (``None`` when not armed);
- ``signals()`` — the placement/health snapshot a scrape produces:
  ``ready`` (warmup done, accepting), ``alive``, ``hbm_free_bytes``
  (KV headroom), ``queue_depth``, ``generation``, ``firing`` (alert
  rule names);
- ``is_alive()`` / ``kill()`` / ``drain()`` / ``respawn()`` — liveness
  and the lifecycle verbs the supervisor and controller drive.

Three handle kinds:

- :class:`InProcessReplica` — engine + server in this process
  (tests); ``signals()`` reads the engine
  directly because in-process engines share one global metrics
  registry (their unlabeled gauges clobber each other — scraping HTTP
  here would read whichever engine wrote last).
- :class:`SubprocessReplica` — ``python -m
  consensusml_tpu.fleet.replicas --artifact DIR`` child; signals come
  from the child's HTTP plane via :class:`ExternalReplica` scraping.
- :class:`ExternalReplica` — an already-running server reached only by
  address (attach mode); scrapes ``/healthz`` + ``/metrics`` +
  ``/alerts``.

:class:`ReplicaSet` supervises a fleet of handles: its ``fleet-supervise``
thread detects death (process exit, spawn failure, a kill) and respawns
— the router keeps re-dispatching while the replacement warms up, so a
replica killed mid-traffic loses zero accepted streams (docs/fleet.md).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any

from consensusml_tpu.analysis import guarded_by

__all__ = [
    "ExternalReplica",
    "InProcessReplica",
    "ReplicaSet",
    "SubprocessReplica",
    "scrape_signals",
]

# /metrics families a fleet scrape reads (docs/observability.md): the
# placement signals and the canary's generation/swap observables
_SCRAPE_FAMILIES = (
    "consensusml_pool_hbm_free_bytes",
    "consensusml_serve_queue_depth",
    "consensusml_serve_generation",
    "consensusml_serve_swap_rejected_total",
)


def _fleet_metrics():
    """The replica-lifecycle counter family (registered once; the
    registry dedupes by name)."""
    from consensusml_tpu.obs import get_registry

    reg = get_registry()
    return {
        "spawns": reg.counter(
            "consensusml_fleet_spawns_total",
            "replica spawns (initial + supervisor restarts)",
        ),
        "restarts": reg.counter(
            "consensusml_fleet_restarts_total",
            "replicas respawned after kill/crash detection",
        ),
        "drains": reg.counter(
            "consensusml_fleet_drains_total",
            "graceful replica drains driven by the controller/supervisor",
        ),
    }


def _http_json(url: str, timeout: float = 1.0) -> tuple[int, dict]:
    """GET a JSON endpoint; returns (status, doc). 503s still parse —
    /healthz carries its reason either way."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except Exception:
            return e.code, {}


def _parse_prom(text: str, families: tuple[str, ...]) -> dict[str, float]:
    """Minimal Prometheus text parse: the LAST sample of each wanted
    family wins (unlabeled serving gauges have exactly one)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        if name not in families:
            continue
        try:
            out[name] = float(line.rsplit(" ", 1)[1])
        except ValueError:
            continue
    return out


def scrape_signals(
    metrics_address: tuple[str, int] | None, timeout: float = 1.0
) -> dict[str, Any]:
    """One HTTP scrape of a replica's observability plane →  the
    signal dict placement scores on. Unreachable ⇒ not ready (a dead
    metrics plane means the router must stop placing there)."""
    sig: dict[str, Any] = {
        "ready": False,
        "alive": False,
        "hbm_free_bytes": None,
        "queue_depth": None,
        "generation": None,
        "swap_rejected_total": None,
        "firing": [],
    }
    if metrics_address is None:
        return sig
    host, port = metrics_address
    base = f"http://{host}:{port}"
    try:
        _code, hz = _http_json(f"{base}/healthz", timeout)
        sig["alive"] = True
        sig["ready"] = bool(hz.get("ok"))
        with urllib.request.urlopen(f"{base}/metrics", timeout=timeout) as r:
            fams = _parse_prom(r.read().decode(), _SCRAPE_FAMILIES)
        def _finite(v):
            # untouched gauges expose NaN until first set — scraped
            # non-finite values must land as "absent", never NaN
            return float(v) if v is not None and v == v else None

        sig["hbm_free_bytes"] = _finite(
            fams.get("consensusml_pool_hbm_free_bytes")
        )
        sig["queue_depth"] = _finite(
            fams.get("consensusml_serve_queue_depth")
        )
        sig["generation"] = _finite(fams.get("consensusml_serve_generation"))
        sig["swap_rejected_total"] = _finite(
            fams.get("consensusml_serve_swap_rejected_total")
        )
        code, al = _http_json(f"{base}/alerts", timeout)
        if code == 200:
            sig["firing"] = sorted(
                {a.get("rule") for a in al.get("firing", []) if a.get("rule")}
            )
    except Exception:
        sig["ready"] = False
    return sig


class ExternalReplica:
    """A replica reached only over HTTP (attach mode / subprocess
    child): signals come from scraping its observability plane."""

    def __init__(
        self,
        address: tuple[str, int],
        metrics_address: tuple[str, int] | None = None,
        name: str = "external",
    ):
        self.name = name
        self.address: tuple[str, int] | None = tuple(address)
        self.metrics_address = (
            tuple(metrics_address) if metrics_address else None
        )
        self.artifact: str | None = None

    def signals(self) -> dict[str, Any]:
        if self.metrics_address is None:
            # no metrics plane to consult: assume ready while the
            # front-end address exists (plain L4 semantics)
            return {
                "ready": self.address is not None,
                "alive": self.address is not None,
                "hbm_free_bytes": None,
                "queue_depth": None,
                "generation": None,
                "swap_rejected_total": None,
                "firing": [],
            }
        return scrape_signals(self.metrics_address)

    def is_alive(self) -> bool:
        return True  # liveness is the owner's problem in attach mode

    def kill(self) -> None:
        raise RuntimeError("cannot kill an attached external replica")

    def drain(self, timeout: float | None = None) -> bool:
        raise RuntimeError("cannot drain an attached external replica")

    def respawn(self, block: bool = True) -> None:
        raise RuntimeError("cannot respawn an attached external replica")


@guarded_by("_lock", "_engine", "_server", "_phase", "_injected")
class InProcessReplica:
    """Engine + :class:`ServeServer` in this process.

    ``engine_factory()`` builds a fresh engine per (re)spawn — the
    respawn path constructs a NEW engine (new jit wrappers, fresh
    warmup), exactly like a restarted process would. Spawn runs on the
    ``fleet-replica-spawn`` thread because warmup pays multi-second
    compiles; the replica is not ready (and has no address) until it
    completes, which is the readiness gate the router scrapes.
    """

    def __init__(
        self,
        engine_factory,
        *,
        name: str,
        artifact: str | None = None,
        warmup: bool = True,
        watch_poll_s: float = 0.1,
    ):
        self.name = name
        self.artifact = artifact
        self._factory = engine_factory
        self._do_warmup = warmup
        self._watch_poll_s = watch_poll_s
        self._lock = threading.Lock()
        self._engine: Any = None
        self._server: Any = None
        # new -> spawning -> ready -> draining|dead|failed
        self._phase = "new"
        self._spawn_thread: threading.Thread | None = None
        # injected alert rule names (tests drive the controller's
        # canary rollback without waiting out a real burn window)
        self._injected: list[str] = []
        self.restarts = 0
        self.warm_compile_counts: dict[str, int] | None = None
        self._m = _fleet_metrics()

    # -- lifecycle ----------------------------------------------------------
    def spawn(self, block: bool = True, timeout: float = 300.0) -> None:
        with self._lock:
            if self._phase in ("spawning", "ready"):
                raise RuntimeError(f"replica {self.name} already {self._phase}")
            self._phase = "spawning"
        t = threading.Thread(
            target=self._spawn, name="fleet-replica-spawn", daemon=True
        )
        self._spawn_thread = t
        self._m["spawns"].inc()
        t.start()
        if block:
            t.join(timeout)
            if not self.is_ready() and self.phase != "spawning":
                raise RuntimeError(f"replica {self.name} failed to spawn")

    def _spawn(self) -> None:
        try:
            engine = self._factory()
            if self._do_warmup:
                self.warm_compile_counts = dict(engine.warmup())
            if self.artifact is not None:
                engine.watch(self.artifact, poll_s=self._watch_poll_s)
            from consensusml_tpu.serve.server import ServeServer

            server = ServeServer(engine)
        except Exception:
            with self._lock:
                self._phase = "failed"
            return
        with self._lock:
            self._engine, self._server = engine, server
            self._phase = "ready"

    def kill(self) -> None:
        """Abrupt death: close the listener, cancel in-flight streams
        (their connections see ``finish_reason="cancelled"`` terminal
        records — the router's re-dispatch trigger), no drain."""
        with self._lock:
            server, self._server = self._server, None
            engine, self._engine = self._engine, None
            self._phase = "dead"
        if server is not None:
            server.shutdown(drain=False, timeout=2.0)
        elif engine is not None:
            engine.shutdown(drain=False, timeout=2.0)

    def drain(self, timeout: float | None = None) -> bool:
        """Graceful stop: serve everything accepted to completion, then
        close (the controller's SIGTERM-equivalent for this handle)."""
        with self._lock:
            if self._phase != "ready":
                return True
            self._phase = "draining"
            server = self._server
        self._m["drains"].inc()
        server.shutdown(drain=True, timeout=timeout)
        with self._lock:
            self._server, self._engine = None, None
            self._phase = "dead"
        return True

    def respawn(self, block: bool = True) -> None:
        with self._lock:
            self._phase = "new"
        self.restarts += 1
        self._m["restarts"].inc()
        self.spawn(block=block)

    # -- introspection ------------------------------------------------------
    @property
    def phase(self) -> str:
        with self._lock:
            return self._phase

    @property
    def address(self) -> tuple[str, int] | None:
        with self._lock:
            return self._server.address if self._server is not None else None

    @property
    def metrics_address(self) -> tuple[str, int] | None:
        with self._lock:
            s = self._server
        return getattr(s, "metrics_address", None)

    @property
    def engine(self) -> Any:
        with self._lock:
            return self._engine

    def is_alive(self) -> bool:
        return self.phase in ("spawning", "ready", "draining")

    def is_ready(self) -> bool:
        return self.phase == "ready"

    def inject_alert(self, rule: str) -> None:
        """Test hook: make ``signals()["firing"]`` report ``rule``
        — drives the controller's rollback path deterministically."""
        with self._lock:
            self._injected.append(rule)

    def clear_alerts(self) -> None:
        with self._lock:
            self._injected.clear()

    def signals(self) -> dict[str, Any]:
        with self._lock:
            engine = self._engine
            phase = self._phase
            firing = list(self._injected)
        sig: dict[str, Any] = {
            "ready": False,
            "alive": phase in ("spawning", "ready", "draining"),
            "hbm_free_bytes": None,
            "queue_depth": None,
            "generation": None,
            "swap_rejected_total": None,
            "firing": firing,
        }
        if engine is None or phase != "ready":
            return sig
        sig["ready"] = bool(getattr(engine, "warmed", True))
        try:
            sig["queue_depth"] = engine._queue.qsize()
            sig["generation"] = engine.generation
            pool = getattr(engine, "_pool", None)
            if pool is not None:
                # same formula as the consensusml_pool_hbm_free_bytes
                # gauge — read directly because in-process engines share
                # one registry (the gauge holds whichever engine's value
                # landed last)
                sig["hbm_free_bytes"] = (
                    pool.free_blocks * engine._block_nbytes
                )
        except Exception:
            sig["ready"] = False
        return sig


class SubprocessReplica:
    """One replica per child process: ``python -m
    consensusml_tpu.fleet.replicas --artifact DIR`` loads the engine,
    warms up, then prints one ``FLEET_REPLICA {...}`` line with its
    bound addresses — the parent's ``fleet-replica-io`` thread parses
    it and the handle becomes ready. Signals scrape the child's HTTP
    plane (its own process ⇒ its own registry — no gauge collisions)."""

    def __init__(
        self,
        artifact: str,
        *,
        name: str,
        slots: int = 4,
        max_new_tokens: int = 16,
        host: str = "127.0.0.1",
        extra_args: list[str] | None = None,
    ):
        self.name = name
        self.artifact = os.path.abspath(artifact)
        self._slots = slots
        self._max_new = max_new_tokens
        self._host = host
        self._extra_args = list(extra_args or [])
        self._proc: subprocess.Popen | None = None
        self._io_thread: threading.Thread | None = None
        self._ready = threading.Event()
        self.address: tuple[str, int] | None = None
        self.metrics_address: tuple[str, int] | None = None
        self.platform: str | None = None  # the child's jax backend, once ready
        self.restarts = 0
        self._m = _fleet_metrics()

    def spawn(self, block: bool = True, timeout: float = 300.0) -> None:
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        cmd = [
            sys.executable, "-m", "consensusml_tpu.fleet.replicas",
            "--artifact", self.artifact, "--host", self._host,
            "--slots", str(self._slots), "--max-new", str(self._max_new),
        ] + self._extra_args
        self._ready.clear()
        self.address = None
        self.metrics_address = None
        self._m["spawns"].inc()
        self._proc = subprocess.Popen(
            cmd,
            stdout=subprocess.PIPE,
            stderr=None,  # child stderr rides the parent's (crash triage)
            text=True,
            cwd=repo_root,
        )
        t = threading.Thread(
            target=self._read_stdout, name="fleet-replica-io", daemon=True
        )
        self._io_thread = t
        t.start()
        if block and not self._ready.wait(timeout):
            raise RuntimeError(
                f"replica {self.name} not ready after {timeout}s"
            )

    def _read_stdout(self) -> None:
        proc = self._proc
        assert proc is not None and proc.stdout is not None
        for line in proc.stdout:
            if line.startswith("FLEET_REPLICA "):
                try:
                    doc = json.loads(line[len("FLEET_REPLICA "):])
                    self.address = tuple(doc["address"])
                    ma = doc.get("metrics")
                    self.metrics_address = tuple(ma) if ma else None
                    self.platform = doc.get("platform")
                    self._ready.set()
                except (ValueError, KeyError):
                    pass

    def signals(self) -> dict[str, Any]:
        if not self._ready.is_set() or not self.is_alive():
            return {
                "ready": False, "alive": self.is_alive(),
                "hbm_free_bytes": None, "queue_depth": None,
                "generation": None, "swap_rejected_total": None,
                "firing": [],
            }
        return scrape_signals(self.metrics_address)

    def is_alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    def is_ready(self) -> bool:
        return self._ready.is_set() and self.is_alive()

    def kill(self) -> None:
        if self._proc is not None:
            self._proc.kill()
            self._proc.wait(timeout=10)

    def drain(self, timeout: float | None = None) -> bool:
        """SIGTERM → the child's ``install_sigterm`` drain path."""
        if self._proc is None or self._proc.poll() is not None:
            return True
        self._m["drains"].inc()
        self._proc.terminate()
        try:
            self._proc.wait(timeout=timeout if timeout else 60)
            return True
        except subprocess.TimeoutExpired:
            self._proc.kill()
            return False

    def respawn(self, block: bool = True) -> None:
        self.restarts += 1
        self._m["restarts"].inc()
        self.spawn(block=block)


@guarded_by("_lock", "_replicas")
class ReplicaSet:
    """The supervised fleet: holds the replica handles the router and
    controller share, and (when supervision is started) restarts dead
    ones on the ``fleet-supervise`` thread. A replica is *dead* when it
    reported ready once and ``is_alive()`` went false — spawn failures
    surface as ``failed`` phases the owner must inspect, not silent
    respawn loops."""

    def __init__(self, replicas, *, restart: bool = True, poll_s: float = 0.25):
        self._lock = threading.Lock()
        self._replicas = list(replicas)
        self.restart = restart
        self.poll_s = poll_s
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._was_ready: set[str] = set()  # supervise-thread only
        self._m = _fleet_metrics()

    def replicas(self) -> list:
        with self._lock:
            return list(self._replicas)

    def add(self, replica) -> None:
        with self._lock:
            self._replicas.append(replica)

    def spawn_all(self, block: bool = True) -> None:
        reps = self.replicas()
        subs = [r for r in reps if isinstance(r, SubprocessReplica)]
        if len(subs) > 1:
            # a chip belongs to one process and every child inherits all
            # of them: learn what the first child runs on (this process
            # stays off jax) before starting siblings that would hang or
            # die opening chips the first one holds
            subs[0].spawn(block=True)
            if subs[0].platform == "tpu":
                subs[0].kill()
                raise RuntimeError(
                    f"{len(subs)} subprocess replicas on a TPU host: each "
                    "child process would open every chip, and a chip "
                    "belongs to one process. Run the replicas in-process "
                    "(InProcessReplica, one device each) or one "
                    "subprocess replica per host"
                )
            reps = [r for r in reps if r is not subs[0]]
        for r in reps:
            r.spawn(block=False)
        if block:
            deadline = time.time() + 600.0
            for r in reps:
                while not r.is_ready() and time.time() < deadline:
                    if hasattr(r, "phase") and r.phase == "failed":
                        raise RuntimeError(f"replica {r.name} failed to spawn")
                    time.sleep(0.05)

    def start_supervision(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._supervise, name="fleet-supervise", daemon=True
        )
        self._thread.start()

    def _supervise(self) -> None:
        while not self._stop.wait(self.poll_s):
            for r in self.replicas():
                if r.is_ready():
                    self._was_ready.add(r.name)
                elif (
                    r.name in self._was_ready
                    and not r.is_alive()
                    and self.restart
                ):
                    self._was_ready.discard(r.name)
                    try:
                        # block: one respawn at a time keeps the warmup
                        # compile storm bounded; the router keeps
                        # re-dispatching around the hole meanwhile
                        r.respawn(block=True)
                    except Exception:
                        pass  # stays dead; next poll retries nothing

    def stop(self, drain: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(2.0, 4 * self.poll_s))
            self._thread = None
        for r in self.replicas():
            try:
                if drain:
                    r.drain(timeout=30)
                else:
                    r.kill()
            except RuntimeError:
                pass  # external handles have no lifecycle verbs


def main(argv=None) -> int:
    """Child-process entry: serve one replica from an artifact.

    Order matters for the readiness story: the server (and its
    ``/healthz``) comes up FIRST — reporting not-ready — then warmup
    runs, then the ready line prints. A router polling from t=0 sees
    503 until the replica can actually take traffic.
    """
    import argparse

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--artifact", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--metrics-port", type=int, default=0)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--obs-tick-s", type=float, default=1.0)
    p.add_argument("--watch-poll-s", type=float, default=0.25)
    p.add_argument("--prefix-cache", action="store_true")
    args = p.parse_args(argv)

    import jax

    from consensusml_tpu.compile_cache import enable_compile_cache
    from consensusml_tpu.serve import ServeConfig, load_engine
    from consensusml_tpu.serve.server import ServeServer

    enable_compile_cache()
    engine = load_engine(
        args.artifact,
        ServeConfig(
            num_slots=args.slots,
            max_new_tokens=args.max_new,
            prefix_cache=args.prefix_cache,
        ),
    )
    server = ServeServer(
        engine,
        host=args.host,
        port=args.port,
        metrics_port=args.metrics_port,
        obs_tick_s=args.obs_tick_s,
    )
    server.install_sigterm()
    engine.warmup()
    engine.watch(args.artifact, poll_s=args.watch_poll_s)
    print(
        "FLEET_REPLICA "
        + json.dumps(
            {
                "address": list(server.address),
                "metrics": (
                    list(server.metrics_address)
                    if server.metrics_address
                    else None
                ),
                "artifact": os.path.abspath(args.artifact),
                "pid": os.getpid(),
                "platform": jax.default_backend(),
            }
        ),
        flush=True,
    )
    # serve until SIGTERM/SIGINT lands (install_sigterm drains); the
    # engine loop thread is the real worker — this thread just waits
    try:
        while engine._thread.is_alive():
            engine._thread.join(timeout=1.0)
    except KeyboardInterrupt:
        server.shutdown(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
