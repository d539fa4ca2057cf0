"""Placement-aware line-JSON TCP router over N serving replicas.

The router speaks the exact :class:`~consensusml_tpu.serve.server.
ServeServer` wire protocol on both sides — clients connect to it as if
it were one big server, and it proxies each stream to a replica chosen
by **score**, not rotation:

    score(replica) = hbm_free_bytes / (1 + queue_depth)

over the signals its ``fleet-scrape`` thread collects from every
replica handle (``/healthz`` readiness, ``consensusml_pool_hbm_free_bytes``
KV headroom, ``consensusml_serve_queue_depth``). A not-ready replica —
503, stale scrape, still paying warmup compiles — scores ``-inf`` and
takes **zero** new streams. Ties (and pools without a headroom gauge)
fall back to least-queue-depth, then name order, so placement is
deterministic for a given signal snapshot. ``policy="round_robin"``
keeps the rotation baseline
(``tests/test_fleet.py::test_router_round_robin_rotates_over_ready_set``).

**Affinity**: each request's ``(tenant, prompt-prefix-hash)`` key
(sha-256 over the first ``affinity_tokens`` prompt ids) remembers the
replica that served it last, and repeats land there while it stays
ready and its queue is shallow — that replica's
:class:`~consensusml_tpu.serve.pool.prefix.PrefixIndex` already holds
the prefix blocks, so affinity is what makes fleet prefix hit-rate
track single-engine hit-rate (docs/fleet.md).

**Re-dispatch**: a queue-full reject, a dead connection, or a stream
that ends in ``finish_reason="cancelled"`` (the replica was killed
mid-stream) re-dispatches to the next-best replica with bounded
retries + exponential backoff — as a **continuation**: the retried
request's prompt is ``ids + tokens_streamed_so_far`` with the token
budget reduced, so the client's stream resumes exactly where it broke
and an accepted stream is never lost (``lost_streams == 0``, held by
``tests/test_fleet.py``'s re-dispatch tests).
"""

from __future__ import annotations

import collections
import hashlib
import json
import socket
import threading
import time
from typing import Any

from consensusml_tpu.analysis import guarded_by

__all__ = ["FleetRouter", "affinity_key", "placement_score"]


def affinity_key(tenant: str | None, ids, n_tokens: int = 16) -> str:
    """The (tenant, prompt-prefix-hash) placement key: requests sharing
    a system prompt (and tenant) hash identically and ride the same
    replica's prefix index."""
    h = hashlib.sha256()
    h.update((tenant or "default").encode())
    for t in list(ids)[:n_tokens]:
        h.update(int(t).to_bytes(4, "little", signed=True))
    return h.hexdigest()[:16]


def placement_score(sig: dict[str, Any]) -> tuple[float, float]:
    """Sortable per-replica score (higher is better): KV headroom per
    queued request first, raw queue depth as the tiebreak. ``ready``
    must already be checked — this orders the READY candidates."""
    # a missing/NaN gauge (a replica that never took a stream exposes
    # NaN until first set) must read as "no signal", not poison the
    # sort tuple — NaN is truthy and orders ill-defined under max()
    q = sig.get("queue_depth")
    q = float(q) if q is not None and q == q else 0.0
    hbm = sig.get("hbm_free_bytes")
    head = float(hbm) if hbm is not None and hbm == hbm else 0.0
    return (head / (1.0 + q), -q)


@guarded_by(
    "_lock", "_signals", "_affinity", "_rr_next", "_conns", "_counts",
    "_place_s",
)
class FleetRouter:
    """Threaded front-end: accept loop + one thread per client stream +
    the signal scrape loop. ``fleet`` is a
    :class:`~consensusml_tpu.fleet.replicas.ReplicaSet` (anything with
    ``replicas() -> [handle]`` works); ``port=0`` picks a free port
    (read :attr:`address` back)."""

    def __init__(
        self,
        fleet,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        policy: str = "score",
        scrape_s: float = 0.25,
        max_retries: int = 6,
        backoff_s: float = 0.1,
        affinity_tokens: int = 16,
        affinity_max_queue: int = 16,
        upstream_timeout_s: float = 120.0,
    ):
        if policy not in ("score", "round_robin"):
            raise ValueError(f"unknown placement policy {policy!r}")
        self.fleet = fleet
        self.policy = policy
        self.scrape_s = float(scrape_s)
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.affinity_tokens = int(affinity_tokens)
        self.affinity_max_queue = int(affinity_max_queue)
        self.upstream_timeout_s = float(upstream_timeout_s)

        from consensusml_tpu.obs import get_registry

        reg = get_registry()
        self._reg = reg
        self._m_redispatch = reg.counter(
            "consensusml_fleet_redispatch_total",
            "streams re-dispatched to another replica (queue-full "
            "reject, dead connection, or mid-stream replica death)",
        )
        self._m_rejected = reg.counter(
            "consensusml_fleet_rejected_total",
            "streams refused after exhausting placement retries",
        )
        self._m_affinity = reg.counter(
            "consensusml_fleet_affinity_hits_total",
            "placements that honored the (tenant, prefix-hash) affinity",
        )
        self._m_ready = reg.gauge(
            "consensusml_fleet_replicas_ready",
            "replicas currently taking new streams",
        )
        self._m_place = reg.histogram(
            "consensusml_fleet_placement_seconds",
            "placement decision wall time per landed dispatch (scoring "
            "the scraped snapshot + affinity lookup) — the router's "
            "per-stream logic overhead",
        )
        self._placements: dict[str, Any] = {}  # accept/conn threads only via _lock

        self._lock = threading.Lock()
        self._signals: dict[str, tuple[Any, dict]] = {}
        self._affinity: "collections.OrderedDict[str, str]" = (
            collections.OrderedDict()
        )
        self._rr_next = 0
        self._conns: set[threading.Thread] = set()
        self._counts = {
            "accepted": 0, "completed": 0, "rejected": 0,
            "client_gone": 0, "redispatches": 0, "affinity_hits": 0,
            "placements": collections.Counter(),
        }
        self._place_s: collections.deque = collections.deque(maxlen=4096)

        self._stop = threading.Event()
        self._scrape_once()
        # listener binds before the threads exist: a taken port raises
        # with nothing to clean up
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(128)
        self._sock.settimeout(0.2)
        self.address = self._sock.getsockname()
        self._scraper = threading.Thread(
            target=self._scrape_loop, name="fleet-scrape", daemon=True
        )
        self._scraper.start()
        self._thread = threading.Thread(
            target=self._accept_loop, name="fleet-router-accept", daemon=True
        )
        self._thread.start()

    # -- signal scrape ------------------------------------------------------
    def _scrape_loop(self) -> None:
        while not self._stop.wait(self.scrape_s):
            self._scrape_once()

    def _scrape_once(self) -> None:
        """Collect every replica's signals OUTSIDE the router lock
        (handles take their own locks / do HTTP I/O), then publish the
        snapshot atomically."""
        sigs: dict[str, tuple[Any, dict]] = {}
        for r in self.fleet.replicas():
            try:
                sigs[r.name] = (r, r.signals())
            except Exception:
                sigs[r.name] = (r, {"ready": False})
        self._m_ready.set(
            sum(1 for _r, s in sigs.values() if s.get("ready"))
        )
        with self._lock:
            self._signals = sigs

    # -- placement ----------------------------------------------------------
    def _choose(
        self, key: str | None, exclude: set[str]
    ) -> tuple[str, Any] | None:
        """Pick the replica for one (re)dispatch: affinity first (while
        its target is ready and shallow-queued), then best score; the
        round-robin policy rotates over the ready set. Returns
        ``(name, handle)`` or ``None`` when nothing is placeable."""
        with self._lock:
            sigs = dict(self._signals)
            aff_name = self._affinity.get(key) if key else None
        ready = sorted(
            (name, r, s)
            for name, (r, s) in sigs.items()
            if s.get("ready") and name not in exclude and r.address is not None
        )
        if not ready:
            return None
        chosen = None
        if self.policy == "round_robin":
            with self._lock:
                idx = self._rr_next
                self._rr_next = idx + 1
            name, r, _s = ready[idx % len(ready)]
            chosen = (name, r)
        else:
            if aff_name is not None:
                for name, r, s in ready:
                    if name == aff_name and (
                        float(s.get("queue_depth") or 0.0)
                        <= self.affinity_max_queue
                    ):
                        chosen = (name, r)
                        self._m_affinity.inc()
                        with self._lock:
                            self._counts["affinity_hits"] += 1
                        break
            if chosen is None:
                name, r, _s = max(
                    ready, key=lambda t: (placement_score(t[2]), t[0])
                )
                chosen = (name, r)
        if key:
            with self._lock:
                self._affinity[key] = chosen[0]
                self._affinity.move_to_end(key)
                while len(self._affinity) > 8192:
                    self._affinity.popitem(last=False)
        return chosen

    def _record_placement(self, name: str, dt: float) -> None:
        self._m_place.observe(dt)
        m = self._placements.get(name)
        if m is None:
            m = self._placements[name] = self._reg.counter(
                "consensusml_fleet_placements_total",
                "streams placed, per replica",
                labels={"replica": name},
            )
        m.inc()
        with self._lock:
            self._counts["placements"][name] += 1
            self._place_s.append(dt)

    # -- accept / proxy -----------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break  # listener closed under us during shutdown
            t = threading.Thread(
                target=self._proxy_conn, args=(conn,), daemon=True
            )
            with self._lock:
                self._conns.add(t)
            t.start()
        self._sock.close()

    def _proxy_conn(self, conn: socket.socket) -> None:
        try:
            with conn:
                f = conn.makefile("rwb")
                line = f.readline()
                if not line:
                    return
                try:
                    req = json.loads(line)
                    ids = [int(t) for t in req["ids"]]
                except Exception as e:
                    f.write(json.dumps({"error": str(e)}).encode() + b"\n")
                    f.flush()
                    return
                self._bump("accepted")
                try:
                    self._route_stream(req, ids, f)
                except (BrokenPipeError, ConnectionResetError):
                    # the CLIENT went away mid-stream — not a lost
                    # stream, the fleet side kept serving
                    self._bump("client_gone")
        except (BrokenPipeError, ConnectionResetError):
            pass
        finally:
            with self._lock:
                self._conns.discard(threading.current_thread())

    def _route_stream(self, req: dict, ids: list[int], f) -> None:
        """Dispatch (and re-dispatch) one accepted stream until its
        terminal record lands. ``got`` accumulates every token already
        streamed to the client — the continuation prompt on re-dispatch."""
        t0 = time.perf_counter()
        max_new = req.get("max_new_tokens")
        key = affinity_key(
            req.get("tenant"), ids, self.affinity_tokens
        )
        got: list[int] = []
        ttft_s: float | None = None
        tried: set[str] = set()
        redispatches = -1  # first dispatch is not a re-dispatch
        last_err = "no ready replica"
        for attempt in range(self.max_retries):
            if attempt:
                time.sleep(min(self.backoff_s * (2 ** (attempt - 1)), 2.0))
                self._scrape_once()  # a respawn/recovery may have landed
            t_sel = time.perf_counter()
            choice = self._choose(key, tried)
            if choice is None and tried:
                # every known replica failed once — forgive and rescore,
                # a killed replica's replacement may be ready by now
                tried.clear()
                choice = self._choose(key, tried)
            sel_dt = time.perf_counter() - t_sel
            if choice is None:
                continue
            name, replica = choice
            addr = replica.address
            if addr is None:
                tried.add(name)
                continue
            redispatches += 1
            if redispatches:
                self._m_redispatch.inc()
                self._bump("redispatches")
            if max_new is not None and len(got) >= int(max_new):
                # the stream already hit its token budget before the
                # dying replica's terminal record landed: finish it here
                self._finish(
                    f, req, got, ttft_s, t0, redispatches, name,
                    finish_reason="max_tokens",
                )
                return
            status, msg = self._attempt(
                name, replica, addr, req, ids, max_new, got, f, t0,
                sel_dt,
            )
            if status == "done":
                if ttft_s is None:
                    ttft_s = msg.pop("_ttft_s", None)
                else:
                    msg.pop("_ttft_s", None)
                self._finish(
                    f, req, got, ttft_s, t0, redispatches, name,
                    terminal=msg,
                )
                return
            if ttft_s is None and msg and msg.get("_ttft_s") is not None:
                ttft_s = msg["_ttft_s"]
            last_err = (msg or {}).get("error", "replica connection died")
            tried.add(name)
        self._m_rejected.inc()
        self._bump("rejected")
        f.write(
            json.dumps(
                {"error": f"no replica available after "
                          f"{self.max_retries} attempts: {last_err}"}
            ).encode()
            + b"\n"
        )
        f.flush()

    def _attempt(
        self, name, replica, addr, req, ids, max_new, got, f, t0, sel_dt
    ) -> tuple[str, dict | None]:
        """One dispatch to one replica. Streams tokens through to the
        client as they land (appending to ``got``). Returns
        ``("done", terminal_msg)``, ``("rejected", {"error"})`` (replica
        refused pre-stream: queue full / draining), or
        ``("died", {...})`` (connect failure, EOF, or a cancelled
        terminal — the re-dispatch triggers)."""
        creq = dict(req)
        creq["ids"] = ids + got
        if max_new is not None:
            creq["max_new_tokens"] = int(max_new) - len(got)
        ttft_s = None
        try:
            with socket.create_connection(
                addr, timeout=self.upstream_timeout_s
            ) as up:
                # sel_dt is the placement DECISION cost (scoring the
                # scraped snapshot + affinity lookup), recorded only for
                # dispatches that actually land — connect/relay time is
                # the client-visible latency, which loadgen reports separately
                self._record_placement(name, sel_dt)
                uf = up.makefile("rwb")
                uf.write(json.dumps(creq).encode() + b"\n")
                uf.flush()
                for uline in uf:
                    msg = json.loads(uline)
                    if "error" in msg:
                        return "rejected", msg
                    if msg.get("done"):
                        if msg.get("finish_reason") == "cancelled":
                            # the replica is dying (kill/non-drain
                            # shutdown cancels in-flight streams): treat
                            # as a dead connection and re-dispatch the
                            # continuation
                            return "died", {"_ttft_s": ttft_s}
                        msg["_ttft_s"] = ttft_s
                        return "done", msg
                    tok = int(msg["token"])
                    if ttft_s is None:
                        ttft_s = time.perf_counter() - t0
                    got.append(tok)
                    f.write(json.dumps({"token": tok}).encode() + b"\n")
                    f.flush()
            return "died", {"_ttft_s": ttft_s}  # EOF without a terminal
        except (BrokenPipeError, ConnectionResetError):
            raise  # client-side break: the caller counts it
        except (OSError, ValueError) as e:
            return "died", {"_ttft_s": ttft_s, "error": str(e)}

    def _finish(
        self, f, req, got, ttft_s, t0, redispatches, replica_name,
        terminal: dict | None = None, finish_reason: str | None = None,
    ) -> None:
        """Write the stream's terminal record: the replica's own record
        with tokens replaced by the FULL (possibly multi-replica)
        stream, timing re-measured at the router (the client-visible
        truth spans every dispatch), and fleet fields appended."""
        out = dict(terminal or {})
        out.pop("_ttft_s", None)
        out["done"] = True
        out["tokens"] = list(got)
        if finish_reason is not None:
            out["finish_reason"] = finish_reason
        now = time.perf_counter()
        out["ttft_ms"] = round(
            1e3 * (ttft_s if ttft_s is not None else now - t0), 3
        )
        out["latency_ms"] = round(1e3 * (now - t0), 3)
        out["redispatches"] = redispatches
        out["replica"] = replica_name
        out.setdefault("trace_id", req.get("trace_id", ""))
        out.setdefault("request_id", req.get("request_id", ""))
        # count the completion BEFORE flushing the terminal: report()
        # must never show a stream as lost once its client holds the
        # terminal record (a caller may read report() the instant its
        # client returns). A client that vanished at the last byte still
        # completed fleet-side — swallow here so _proxy_conn does not
        # double-count it as client_gone.
        self._bump("completed")
        try:
            f.write(json.dumps(out).encode() + b"\n")
            f.flush()
        except (BrokenPipeError, ConnectionResetError):
            pass

    # -- accounting ---------------------------------------------------------
    def _bump(self, key: str) -> None:
        with self._lock:
            self._counts[key] += 1

    def report(self) -> dict[str, Any]:
        """Fleet-side stream accounting for the obs snapshot:
        ``lost_streams`` is the acceptance-criteria gate — accepted
        streams that neither completed, were refused with an error
        record, nor lost their client."""
        import numpy as np

        with self._lock:
            c = {
                k: (dict(v) if isinstance(v, collections.Counter) else v)
                for k, v in self._counts.items()
            }
            place = list(self._place_s)
        c["lost_streams"] = (
            c["accepted"] - c["completed"] - c["rejected"] - c["client_gone"]
        )
        c["policy"] = self.policy
        c["placement_mean_s"] = float(np.mean(place)) if place else 0.0
        c["placement_p99_s"] = (
            float(np.percentile(place, 99)) if place else 0.0
        )
        return c

    def shutdown(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._scraper.join(timeout=max(2.0, 4 * self.scrape_s))
        with self._lock:
            conns = list(self._conns)
        for t in conns:  # let in-flight streams flush their terminals
            t.join(timeout=5.0)
