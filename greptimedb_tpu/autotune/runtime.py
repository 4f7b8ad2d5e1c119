"""Controller runtime: one low-frequency tick loop per process.

The loop rides the concurrency facade (gtsan-instrumentable thread +
event; no bare threading), times each tick as the background task
``autotune.tick`` (telemetry/tracing.py background_span: counted, in
the trace ring only when slow), and isolates controllers the way
engine.run_maintenance isolates regions: a controller whose sensor or actuator raises logs the error,
ticks ``gtpu_autotune_controller_errors_total{controller=...}``, and
the REMAINING controllers still run — one bad sensor never kills the
control plane.

Freeze semantics (`ADMIN autotune_freeze()` / `[autotune] enable`):
- disabled (`enable = false`): tick_once is a bit-for-bit no-op —
  no timing, no sensor reads, no knob reads, zero decisions.
- frozen: the loop keeps ticking (timed + counted, so operators can
  see it is alive) but no controller runs and no knob moves;
  ``gtpu_autotune_frozen`` reads 1. ADMIN set_config stays available —
  freezing hands control back to the operator, it does not take the
  update API away.
"""

from __future__ import annotations

import logging

from greptimedb_tpu import concurrency
from greptimedb_tpu.telemetry.metrics import global_registry

_log = logging.getLogger("greptimedb_tpu.autotune")

_FROZEN = global_registry.gauge(
    "gtpu_autotune_frozen",
    "1 while the control plane is frozen (ADMIN autotune_freeze)",
)
_TICKS = global_registry.counter(
    "gtpu_autotune_ticks_total",
    "controller-runtime ticks (frozen ticks included)",
)
_ERRORS = global_registry.counter(
    "gtpu_autotune_controller_errors_total",
    "controller ticks that raised (isolated; the loop continues)",
    labels=("controller",),
)


class AutotuneRuntime:
    """The per-process control loop over a controller set."""

    def __init__(self, knobs, controllers, *, interval_s: float = 5.0,
                 enabled: bool = False):
        self.knobs = knobs
        self.controllers = list(controllers)
        self.interval_s = float(interval_s)
        self.enabled = bool(enabled)
        self._frozen = False
        self._stop = concurrency.Event()
        self._thread = None

    # ---- configuration ------------------------------------------------
    def apply_options(self, section: dict | None) -> None:
        """Apply the `[autotune]` TOML section: master + per-controller
        enables, tick cadence, shared guardrails."""
        o = section or {}
        self.enabled = bool(o.get("enable", False))
        self.interval_s = float(o.get("tick_interval_s", self.interval_s))
        for c in self.controllers:
            c.enabled = bool(o.get(c.name, True))
            c.rails.step = float(o.get("step", c.rails.step))
            c.rails.band = float(o.get("band", c.rails.band))
            c.rails.cooldown_ticks = int(
                o.get("cooldown_ticks", c.rails.cooldown_ticks)
            )

    # ---- freeze -------------------------------------------------------
    def freeze(self, on: bool = True) -> None:
        self._frozen = bool(on)
        _FROZEN.set(1.0 if self._frozen else 0.0)

    @property
    def frozen(self) -> bool:
        return self._frozen

    # ---- the tick -----------------------------------------------------
    def tick_once(self) -> int:
        """One control tick; returns applied knob changes. Safe to
        call directly (tests, and the ADMIN surface could expose it)."""
        if not self.enabled:
            return 0
        from greptimedb_tpu.telemetry import tracing

        with tracing.background_span("autotune.tick"):
            _TICKS.inc()
            if self._frozen:
                return 0
            n = 0
            for c in self.controllers:
                try:
                    n += int(c.tick())
                except Exception:  # noqa: BLE001 - per-controller
                    # isolation: one raising sensor/actuator must not
                    # kill the loop or starve the other controllers
                    _ERRORS.labels(c.name).inc()
                    _log.warning("[autotune] controller %r failed "
                                 "this tick", c.name, exc_info=True)
            return n

    # ---- lifecycle ----------------------------------------------------
    def start(self) -> None:
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        # contract: the controller loop is a process-lifetime daemon
        # with no submitting request — its ticks are background tasks,
        # never children of whichever request happened to call start()
        self._thread = concurrency.Thread(
            target=self._run,
            name="gtpu-autotune", daemon=True,
        )
        self._thread.start()
        _log.info("[autotune] control loop started "
                  "(tick every %.1fs, controllers: %s)",
                  self.interval_s,
                  ", ".join(c.name for c in self.controllers
                            if c.enabled) or "none")

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick_once()
            except Exception:  # noqa: BLE001 - the loop must survive
                # anything (tracing teardown, interpreter shutdown
                # races); controller errors are already isolated above
                _log.warning("[autotune] tick failed", exc_info=True)

    def close(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    # ---- audit --------------------------------------------------------
    def decisions(self) -> list[dict]:
        return [c.to_doc() for c in self.knobs.changes()]
