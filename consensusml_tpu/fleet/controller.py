"""Alert-driven fleet control: drain/respawn sick replicas, canary
generation rollout with soak-gated promote/rollback.

The controller consumes the SAME signals the router places on (each
handle's ``signals()``: ``/alerts`` firing rules, generation, swap
rejections) and drives two loops:

**Sick handling** — a replica whose firing set intersects the burn-rate
``sick_rules`` (the PR-14 ruleset: TTFT/inter-token burn, queue
backlog, stale serve loop) for longer than ``sick_after_s`` is drained
(graceful: every accepted stream completes — SIGTERM on a subprocess
replica, ``ServeServer.shutdown(drain=True)`` in-process) and
respawned. The router's scrape sees the drain as not-ready and places
zero new streams there while it happens.

**Canary rollout** — the state machine (docs/fleet.md)::

    IDLE --start_canary()--> SOAKING --healthy soak--> PROMOTED
                                 |
                                 +--bad signal-------> ROLLED_BACK

``start_canary()`` bumps the artifact generation on ONE ready replica
and records the pre-canary meta. During the soak window the controller
watches that replica's signals: a firing ``canary_bad_rules`` alert
(``spec-acceptance-collapse``, ``swap-rejections``) or a growing
``consensusml_serve_swap_rejected_total`` rolls back — the old meta is
re-pinned FORWARD (:func:`~consensusml_tpu.serve.export.pin_generation`:
watchers reject regressed generations, so "back" is a new generation
carrying the old content). A soak that lands the swap
(``generation >= target``) with no bad signal through ``soak_s``
promotes: every other replica's artifact is bumped fleet-wide. A swap
that never lands within ``soak_timeout_s`` also rolls back.

Rollback scope: a metadata-only canary (``bump_generation``, same
params — the loadgen flow) rolls back exactly. A NEW-WEIGHTS
canary overwrites the artifact's model directory, so re-pinning the
meta restores the ordering key but not the old bytes — back up the
model dir before a weight canary (docs/fleet.md).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any

from consensusml_tpu.analysis import guarded_by

__all__ = ["CanaryState", "FleetController"]


class CanaryState:
    """Canary rollout states (the ``consensusml_fleet_canary_state``
    gauge exports the numeric code)."""

    IDLE = "idle"
    SOAKING = "soaking"
    PROMOTED = "promoted"
    ROLLED_BACK = "rolled_back"

    CODES = {IDLE: 0, SOAKING: 1, PROMOTED: 2, ROLLED_BACK: 3}


# the PR-14 burn-rate/pressure rules that mark a replica SICK (drain +
# respawn); see obs/alerts.default_ruleset()
DEFAULT_SICK_RULES = (
    "serve-ttft-burn-rate",
    "serve-intertoken-burn-rate",
    "serve-queue-backlog",
    "serve-loop-stale",
)
# rules that kill a canary during its soak window
DEFAULT_CANARY_BAD_RULES = (
    "spec-acceptance-collapse",
    "swap-rejections",
)


@guarded_by("_lock", "_canary", "_sick_since", "_events")
class FleetController:
    """Poll → decide → act. ``step()`` is one deterministic iteration
    (tests drive it directly); ``start()`` runs it on the
    ``fleet-controller`` thread every ``poll_s``."""

    def __init__(
        self,
        fleet,
        *,
        poll_s: float = 0.5,
        sick_rules: tuple[str, ...] = DEFAULT_SICK_RULES,
        sick_after_s: float = 3.0,
        restart_sick: bool = True,
        canary_bad_rules: tuple[str, ...] = DEFAULT_CANARY_BAD_RULES,
        soak_s: float = 5.0,
        soak_timeout_s: float = 60.0,
    ):
        self.fleet = fleet
        self.poll_s = float(poll_s)
        self.sick_rules = frozenset(sick_rules)
        self.sick_after_s = float(sick_after_s)
        self.restart_sick = restart_sick
        self.canary_bad_rules = frozenset(canary_bad_rules)
        self.soak_s = float(soak_s)
        self.soak_timeout_s = float(soak_timeout_s)

        from consensusml_tpu.obs import get_registry

        reg = get_registry()
        self._m_canary_state = reg.gauge(
            "consensusml_fleet_canary_state",
            "canary rollout state (0 idle, 1 soaking, 2 promoted, "
            "3 rolled back)",
        )
        self._m_promotions = reg.counter(
            "consensusml_fleet_canary_promotions_total",
            "canary generations promoted fleet-wide after a healthy soak",
        )
        self._m_rollbacks = reg.counter(
            "consensusml_fleet_canary_rollbacks_total",
            "canary generations rolled back (bad soak signal or the "
            "swap never landed)",
        )
        from consensusml_tpu.fleet.replicas import _fleet_metrics

        self._m = _fleet_metrics()

        self._lock = threading.Lock()
        self._canary: dict[str, Any] | None = None
        self._sick_since: dict[str, float] = {}
        self._events: collections.deque = collections.deque(maxlen=256)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- event log ----------------------------------------------------------
    def _event(self, kind: str, **detail) -> None:
        row = {"time_s": time.time(), "kind": kind, **detail}
        with self._lock:
            self._events.append(row)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    # -- one control iteration ---------------------------------------------
    def step(self, now: float | None = None) -> dict[str, Any]:
        now = time.time() if now is None else now
        reps = self.fleet.replicas()
        sigs = {r.name: r.signals() for r in reps}
        self._check_sick(reps, sigs, now)
        self._advance_canary(reps, sigs, now)
        return {
            "time_s": now,
            "replicas": {
                name: {
                    "ready": bool(s.get("ready")),
                    "queue_depth": s.get("queue_depth"),
                    "hbm_free_bytes": s.get("hbm_free_bytes"),
                    "generation": s.get("generation"),
                    "firing": list(s.get("firing") or []),
                }
                for name, s in sorted(sigs.items())
            },
            "canary": self.canary_status(),
        }

    def _check_sick(self, reps, sigs, now: float) -> None:
        for r in reps:
            firing = self.sick_rules.intersection(
                sigs.get(r.name, {}).get("firing") or []
            )
            if not firing:
                with self._lock:
                    self._sick_since.pop(r.name, None)
                continue
            with self._lock:
                since = self._sick_since.setdefault(r.name, now)
            if now - since < self.sick_after_s or not self.restart_sick:
                continue
            with self._lock:
                self._sick_since.pop(r.name, None)
            self._event("drain", replica=r.name, rules=sorted(firing))
            try:
                r.drain(timeout=60)
                r.respawn(block=False)
                self._event("respawn", replica=r.name)
            except RuntimeError:
                pass  # attach-mode handles have no lifecycle verbs

    # -- canary rollout -----------------------------------------------------
    def start_canary(self, now: float | None = None) -> dict[str, Any]:
        """Bump the artifact generation on ONE ready replica and enter
        the soak window. Returns the canary record."""
        from consensusml_tpu.serve.export import bump_generation, serving_meta

        now = time.time() if now is None else now
        with self._lock:
            if self._canary is not None and (
                self._canary["state"] == CanaryState.SOAKING
            ):
                raise RuntimeError("a canary soak is already in flight")
        candidates = [
            r for r in self.fleet.replicas()
            if r.artifact and r.is_ready()
        ]
        if not candidates:
            raise RuntimeError(
                "no ready replica with an artifact dir to canary"
            )
        victim = candidates[0]
        old_meta = serving_meta(victim.artifact)
        baseline = victim.signals().get("swap_rejected_total")
        target = bump_generation(victim.artifact)
        canary = {
            "state": CanaryState.SOAKING,
            "replica": victim.name,
            "artifact": victim.artifact,
            "old_meta": old_meta,
            "old_generation": int(old_meta.get("generation", 0)),
            "target_generation": target,
            "swap_rejected_baseline": baseline,
            "started_s": now,
        }
        with self._lock:
            self._canary = canary
        self._m_canary_state.set(CanaryState.CODES[CanaryState.SOAKING])
        self._event(
            "canary-start", replica=victim.name, target_generation=target
        )
        return dict(canary)

    def _advance_canary(self, reps, sigs, now: float) -> None:
        with self._lock:
            canary = self._canary
        if canary is None or canary["state"] != CanaryState.SOAKING:
            return
        sig = sigs.get(canary["replica"]) or {}
        bad = self.canary_bad_rules.intersection(sig.get("firing") or [])
        rejected = sig.get("swap_rejected_total")
        baseline = canary.get("swap_rejected_baseline")
        if (
            rejected is not None
            and baseline is not None
            and rejected > baseline
        ):
            bad = bad | {"swap-rejections(gauge)"}
        if bad:
            self._rollback(canary, reason=sorted(bad))
            return
        gen = sig.get("generation")
        swapped = gen is not None and gen >= canary["target_generation"]
        if swapped and now - canary["started_s"] >= self.soak_s:
            self._promote(canary, reps)
        elif not swapped and now - canary["started_s"] > self.soak_timeout_s:
            self._rollback(canary, reason=["swap-never-landed"])

    def _promote(self, canary: dict, reps) -> None:
        """Healthy soak: roll the generation bump out fleet-wide (every
        other replica's artifact dir that has not reached the target)."""
        from consensusml_tpu.serve.export import bump_generation, serving_meta

        target = canary["target_generation"]
        bumped = []
        for r in reps:
            if r.name == canary["replica"] or not r.artifact:
                continue
            try:
                if int(serving_meta(r.artifact).get("generation", 0)) < target:
                    bump_generation(r.artifact)
                    bumped.append(r.name)
            except ValueError:
                continue
        canary = dict(canary, state=CanaryState.PROMOTED, promoted=bumped)
        with self._lock:
            self._canary = canary
        self._m_canary_state.set(CanaryState.CODES[CanaryState.PROMOTED])
        self._m_promotions.inc()
        self._event(
            "canary-promote", replica=canary["replica"],
            target_generation=target, bumped=bumped,
        )

    def _rollback(self, canary: dict, reason: list[str]) -> None:
        """Bad soak: re-pin the pre-canary meta FORWARD on the canary's
        artifact (a new generation carrying the old content — watchers
        reject regressions, so rollback is a forward write)."""
        from consensusml_tpu.serve.export import pin_generation

        pinned = pin_generation(canary["artifact"], canary["old_meta"])
        canary = dict(
            canary,
            state=CanaryState.ROLLED_BACK,
            reason=reason,
            pinned_generation=pinned,
        )
        with self._lock:
            self._canary = canary
        self._m_canary_state.set(CanaryState.CODES[CanaryState.ROLLED_BACK])
        self._m_rollbacks.inc()
        self._event(
            "canary-rollback", replica=canary["replica"], reason=reason,
            pinned_generation=pinned,
        )

    def canary_status(self) -> dict[str, Any]:
        with self._lock:
            canary = self._canary
        if canary is None:
            return {"state": CanaryState.IDLE}
        out = {
            k: v for k, v in canary.items() if k != "old_meta"
        }
        return out

    # -- background loop ----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name="fleet-controller", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            try:
                self.step()
            except Exception:
                pass  # a flaky scrape must not kill the control loop

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(2.0, 4 * self.poll_s))
            self._thread = None
