"""Runtime host↔device transfer auditor (``BCG_TPU_HOSTSYNC``).

Until this module nothing at runtime COUNTED the device→host
round-trips the game loop actually performs:
``BCG-HOST-SYNC`` is a static AST rule over traced regions, blind to
the eager seams (decode readback, ``block_until_ready`` barriers,
``np.asarray`` coercions, the guided parse) where the real per-decision
cost lives.  This auditor closes the gap the way the while-body kernel
census (obs/hlo.py) closed it for kernel counts: observe, attribute,
drift-gate.

Mechanics — two complementary capture paths:

* **Instrumented seams.**  The known materialization points call
  :func:`note` with a site name and the active jit-entry name:
  ``engine/jax_engine.py``'s decode path (prefill barrier, decode-loop
  output readback, step-count readback, speculative draft/accept
  readback) and the FakeEngine's hermetic mirror of the same profile
  (the ``engine.spec.*`` mirror idiom: hermetic games carry the real
  loop's sync structure so the gate can pin calls-per-round without a
  device).  Python cannot intercept ``.block_until_ready()`` or
  ``np.asarray`` centrally (C-level methods on ``jax.Array``), so the
  seams are explicit — which is also what makes each one attributable.
* **``jax.transfer_guard("log")``-style interception.**  When the
  auditor is on, the public ``jax.device_get`` entry point is wrapped
  so untagged materializations through it are still counted (site
  ``device_get``) instead of escaping the audit.  :func:`reset`
  uninstalls the wrapper.

Attribution, per observed sync (acceptance: ≥95% attributed in the
hermetic scenario; the remainder is COUNTED as unattributed, never
dropped):

1. the innermost open tracer span on the calling thread
   (:func:`bcg_tpu.obs.tracer.current` — PR 4's thread-local parent
   machinery), when tracing is on;
2. else the jit-entry name — the explicit ``entry=`` tag a seam
   passes, or the top of the thread-local :func:`jit_entry` stack —
   rendered as ``jit_<entry>`` so the table distinguishes the two;
3. else ``unattributed``.

Surfaces (all zero when the flag is off — no counters registered, no
interception installed, Prometheus exposition and tracer export
byte-identical to an unaudited process; tests/test_hostsync.py pins
the exposition bytes):

* ``engine.hostsync.total`` / ``.attributed`` / ``.unattributed``
  counters, plus ``engine.hostsync.site.<site>`` per seam and the
  attribution table ``engine.hostsync.span.<name>`` — which rides the
  tracer export's embedded counters, so ``scripts/trace_report.py``
  renders "host syncs by span" offline;
* the ``game.host_syncs`` per-round histogram, observed by the
  orchestrator around each ``round`` span;
* the serve ``SchedulerStats`` snapshot's ``hostsync`` block
  (per-dispatch / per-request sync counts);
* ``runtime.metrics.LAST_HOSTSYNC`` (:func:`publish`), so ``bench.py``
  attaches the profile on success AND error paths;
* the ``hostsync`` perf_gate scenario (scripts/perf_gate.py), pinning
  syncs-per-round (hermetic FakeEngine game) and syncs-per-decision
  (tiny real engine) in ``perf_baseline.json`` — the baseline every
  item-2 fusion PR must justify moving, exactly like the while-body
  census did for PRs 8/10.

Flags are read ONCE at first use (per-note env reads would be
measurable on the decode hot path); tests reconfigure via
:func:`reset`.  No jax import at module scope — loadable by flag-only
consumers (bench.py's error path); jax is touched only inside
interception install/uninstall, and only when the auditor is enabled.
"""

from __future__ import annotations

import re
import threading
from typing import Dict, Optional

from bcg_tpu.obs import counters as obs_counters
from bcg_tpu.obs import tracer as obs_tracer
from bcg_tpu.runtime import envflags

# Attribution/site fragments must stay inside the metric-name taxonomy
# ([a-z0-9_] per segment, BCG-OBS-NAME): span names like
# ``serve.request`` flatten to ``serve_request``.
_SANITIZE_RE = re.compile(r"[^a-z0-9_]")

# Per-round sync histogram bounds.  A lockstep round performs a
# handful of syncs per batched engine call (six a round).
ROUND_SYNC_BOUNDS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                     512.0)


def _sanitize(name: str) -> str:
    return _SANITIZE_RE.sub("_", name.lower())


class _NullEntry:
    """Shared no-op context manager — the disabled-auditor fast path
    (the tracer's ``_NullSpan`` idiom)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_ENTRY = _NullEntry()


class _EntryCm:
    """Pushes one jit-entry name onto the calling thread's stack for the
    duration of the block — the tracing-off attribution source."""

    __slots__ = ("_auditor", "_name")

    def __init__(self, auditor: "HostSyncAuditor", name: str):
        self._auditor = auditor
        self._name = name

    def __enter__(self):
        self._auditor._entry_stack().append(self._name)
        return None

    def __exit__(self, exc_type, exc, tb):
        stack = self._auditor._entry_stack()
        if stack:
            stack.pop()
        return False


class _RoundWindow:
    """One open game round's audit window: the auditor total at round
    start, plus whether another round overlapped it (concurrent games —
    see :meth:`HostSyncAuditor.end_round`)."""

    __slots__ = ("start", "overlapped")

    def __init__(self, start: int):
        self.start = start
        self.overlapped = False


class HostSyncAuditor:
    """Process-wide sync recorder; one instance per enabled process
    (module surface below).  All mutation goes through the counter
    registry, so snapshots/deltas/exposition ride the established
    machinery for free."""

    def __init__(self):
        self._local = threading.local()
        self._installed_device_get = None
        self._orig_device_get = None
        # install/uninstall_interception run from serve workers, the
        # scheduler thread, and sweep workers alike; the check-then-act
        # on _installed_device_get must be atomic or two installers can
        # chain-wrap jax.device_get and lose the true original.
        self._install_lock = threading.Lock()
        self._round_lock = threading.Lock()
        self._open_rounds: list = []
        # Register the namespace at construction: an enabled-but-idle
        # process still advertises the audit surface (and the exact-
        # bytes zero-surface test has a definite complement to pin).
        obs_counters.counter("engine.hostsync.total")
        obs_counters.counter("engine.hostsync.attributed")
        obs_counters.counter("engine.hostsync.unattributed")

    # ------------------------------------------------------------ recording

    def _entry_stack(self) -> list:
        stack = getattr(self._local, "entries", None)
        if stack is None:
            stack = self._local.entries = []
        return stack

    def jit_entry(self, name: str) -> _EntryCm:
        return _EntryCm(self, name)

    def current_entry(self) -> Optional[str]:
        stack = getattr(self._local, "entries", None)
        return stack[-1] if stack else None

    def note(self, site: str, n: int = 1, entry: Optional[str] = None) -> None:
        """Record ``n`` device→host materializations at ``site``,
        attributed span-first (innermost open tracer span), then to the
        jit-entry name (explicit ``entry=`` beats the thread-local
        stack), else counted unattributed."""
        if n <= 0:
            return
        span = obs_tracer.current()
        if span is not None:
            attr = _sanitize(span.name)
        else:
            jit = entry if entry is not None else self.current_entry()
            attr = f"jit_{_sanitize(jit)}" if jit else None
        obs_counters.inc("engine.hostsync.total", n)
        obs_counters.inc(f"engine.hostsync.site.{_sanitize(site)}", n)
        if attr is not None:
            obs_counters.inc("engine.hostsync.attributed", n)
            obs_counters.inc(f"engine.hostsync.span.{attr}", n)
        else:
            obs_counters.inc("engine.hostsync.unattributed", n)
            obs_counters.inc("engine.hostsync.span.unattributed", n)

    def total(self) -> int:
        return int(obs_counters.value("engine.hostsync.total"))

    def begin_round(self) -> _RoundWindow:
        """Open one game round's audit window.  Any other round open at
        the same time (concurrent games sharing one serving engine)
        marks BOTH windows overlapped: the process-wide total cannot
        split a shared dispatch batch's syncs between games, and an
        overcounted observation would corrupt the per-round metric."""
        with self._round_lock:
            window = _RoundWindow(self.total())
            if self._open_rounds:
                window.overlapped = True
                for other in self._open_rounds:
                    other.overlapped = True
            self._open_rounds.append(window)
        return window

    def end_round(self, window: _RoundWindow, observe: bool = True) -> None:
        """Close a round window: an unoverlapped round observes its
        exact sync delta into the ``game.host_syncs`` histogram
        (created here — only an enabled auditor ever registers it);
        an overlapped one is COUNTED (``engine.hostsync.rounds_overlapped``)
        rather than observed wrong or dropped silently.

        ``observe=False`` discards the window without recording — the
        failed-round path, which must still REMOVE the window: a leaked
        entry in ``_open_rounds`` would mark every later round
        overlapped and silently stop the histogram for the rest of the
        process."""
        with self._round_lock:
            if window in self._open_rounds:
                self._open_rounds.remove(window)
            syncs = self.total() - window.start
            overlapped = window.overlapped
        if not observe:
            return
        if overlapped:
            obs_counters.inc("engine.hostsync.rounds_overlapped")
        else:
            obs_counters.histogram("game.host_syncs",
                                   ROUND_SYNC_BOUNDS).observe(syncs)
        self.publish()

    # -------------------------------------------------------- interception

    def install_interception(self) -> None:
        """Wrap the public ``jax.device_get`` so materializations that
        bypass the instrumented seams are still counted (site
        ``device_get``).  Failure to import jax degrades to seam-only
        auditing — bench.py's error path must stay loadable."""
        try:
            import jax
        except ImportError:
            return
        with self._install_lock:
            if self._installed_device_get is not None:
                return
            orig = jax.device_get

            def _audited_device_get(x):
                self.note("device_get")
                return orig(x)

            self._orig_device_get = orig
            self._installed_device_get = _audited_device_get
            jax.device_get = _audited_device_get

    def uninstall_interception(self) -> None:
        with self._install_lock:
            if self._installed_device_get is None:
                return
            import jax

            # Only restore if nothing else re-wrapped it after us.
            if jax.device_get is self._installed_device_get:
                jax.device_get = self._orig_device_get
            self._installed_device_get = None
            self._orig_device_get = None

    # ------------------------------------------------------------- reading

    @staticmethod
    def _table(snap: Dict, prefix: str) -> Dict[str, int]:
        return {
            name[len(prefix):]: int(value)
            for name, value in snap.items()
            if name.startswith(prefix)
        }

    def attribution_table(self) -> Dict[str, int]:
        """{attribution name: syncs} — span names as recorded,
        jit-entry attributions under their ``jit_`` prefix, plus
        ``unattributed`` when anything escaped."""
        return self._table(obs_counters.snapshot(),
                           "engine.hostsync.span.")

    def site_table(self) -> Dict[str, int]:
        return self._table(obs_counters.snapshot(),
                           "engine.hostsync.site.")

    def summary(self) -> Dict:
        """The bench-JSON / LAST_HOSTSYNC form: totals, attribution
        coverage, per-site and per-attribution tables, and the
        per-round histogram's count/sum/mean when any round was
        observed.  ONE registry snapshot feeds everything — publish()
        runs this per generation call, so it must not rescan the
        registry per table."""
        snap = obs_counters.snapshot()
        total = int(snap.get("engine.hostsync.total", 0))
        attributed = int(snap.get("engine.hostsync.attributed", 0))
        out: Dict = {
            "total": total,
            "attributed": attributed,
            "unattributed": int(
                snap.get("engine.hostsync.unattributed", 0)
            ),
            "attribution_coverage": (
                round(attributed / total, 4) if total else None
            ),
            "by_site": self._table(snap, "engine.hostsync.site."),
            "by_span": self._table(snap, "engine.hostsync.span."),
        }
        rounds = int(snap.get("game.host_syncs.count", 0))
        if rounds:
            syncs = snap.get("game.host_syncs.sum", 0)
            out["rounds"] = {
                "count": rounds,
                "syncs": int(syncs),
                "syncs_per_round": round(syncs / rounds, 4),
            }
        return out

    def publish(self) -> None:
        """Mirror the summary into ``runtime.metrics.LAST_HOSTSYNC`` so
        bench.py attaches it on success AND error paths (the
        LAST_SERVE_STATS idiom: a mid-wave crash keeps the profile the
        completed calls already recorded)."""
        from bcg_tpu.runtime import metrics

        metrics.publish_hostsync(self.summary())


# ---------------------------------------------------------- module surface
_config_lock = threading.Lock()
_auditor: Optional[HostSyncAuditor] = None
_configured = False


def _ensure() -> Optional[HostSyncAuditor]:
    global _auditor, _configured
    if _configured:
        return _auditor
    with _config_lock:
        if not _configured:
            if envflags.get_bool("BCG_TPU_HOSTSYNC"):
                _auditor = HostSyncAuditor()
                _auditor.install_interception()
            _configured = True
    return _auditor


def auditor() -> Optional[HostSyncAuditor]:
    """The active auditor, or None when auditing is disabled."""
    return _ensure()


def enabled() -> bool:
    return _ensure() is not None


def note(site: str, n: int = 1, entry: Optional[str] = None) -> None:
    """Record ``n`` syncs at ``site`` (module-level seam API; no-op when
    disabled — call sites never need their own guard)."""
    a = _auditor if _configured else _ensure()
    if a is not None:
        a.note(site, n, entry=entry)


def jit_entry(name: str):
    """Context manager labelling the block with a jit-entry name for
    tracing-off attribution; shared no-op when disabled."""
    a = _auditor if _configured else _ensure()
    return a.jit_entry(name) if a is not None else _NULL_ENTRY


def total() -> int:
    a = _auditor if _configured else _ensure()
    return a.total() if a is not None else 0


def summary() -> Optional[Dict]:
    a = _auditor if _configured else _ensure()
    return a.summary() if a is not None else None


def publish() -> None:
    a = _auditor if _configured else _ensure()
    if a is not None:
        a.publish()


def reset() -> None:
    """Uninstall interception and drop the cached auditor + read-once
    flag cache so the next use re-reads the environment — TEST-ONLY.
    Registered ``engine.hostsync.*`` counters persist in the registry
    (live consumers hold baselines); tests needing a pristine registry
    use a subprocess (tests/test_hostsync.py zero-surface pin)."""
    global _auditor, _configured
    with _config_lock:
        if _auditor is not None:
            _auditor.uninstall_interception()
        _auditor = None
        _configured = False
