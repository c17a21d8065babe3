"""Observability: span tracer, counter/gauge registry, and the
device-cost half — HLO census, HBM ledger, telemetry export.

``bcg_tpu.obs.tracer`` — nestable, cross-thread spans with explicit
parent handoff, ring-buffered, exported as Chrome trace-event JSON
(Perfetto-loadable; ``scripts/trace_report.py`` prints the latency
table + top counters from an export).  ``bcg_tpu.obs.counters`` — the
single process-wide counter/gauge/histogram registry (compile/retrace
accounting, the serve latency + SLO-headroom histograms) with
``snapshot()``/``delta()`` for tests and bench JSON.
``bcg_tpu.obs.game_events`` — the consensus-game event stream
(``BCG_TPU_GAME_EVENTS`` JSONL + live ``game.*`` metrics;
``scripts/consensus_report.py`` aggregates the files into
convergence tables).  ``bcg_tpu.obs.hlo`` — lowered-HLO kernel census per jit
entry (``engine.hlo.*`` gauges; ``scripts/hlo_census.py`` +
``hlo_baseline.json`` pin kernel counts per decode step).
``bcg_tpu.obs.ledger`` — per-device HBM byte accounting of params / KV
slabs / prefix entries / spec slots (``hbm.*`` gauges).
``bcg_tpu.obs.export`` — Prometheus text exposition, the
``BCG_TPU_SERVE_EVENTS`` request-lifecycle JSONL sink, and the
``BCG_TPU_METRICS_PORT`` HTTP ``/metrics`` endpoint.
``bcg_tpu.obs.hostsync`` — runtime host↔device transfer auditor
(``BCG_TPU_HOSTSYNC``): per-sync span/jit-entry attribution
(``engine.hostsync.*``), the ``game.host_syncs`` per-round histogram,
and the perf_gate ``hostsync`` drift gate on syncs per round.

None of these modules import jax at module scope: flag-only consumers
(bench.py's error path) stay light.  Enable tracing with
``BCG_TPU_TRACE=1``; see DESIGN.md "Observability" for the span
taxonomy and the device-cost subsection.
"""

from bcg_tpu.obs import counters, export, hlo, hostsync, ledger, tracer  # noqa: F401

# game_events is NOT imported eagerly: it pulls game.statistics, which
# flag-only consumers never need; the orchestrator imports it directly.
__all__ = ["counters", "export", "hlo", "hostsync", "ledger", "tracer"]
