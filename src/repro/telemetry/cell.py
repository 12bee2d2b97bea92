"""The serving cell's metric vocabulary (``cell_*``).

One place defines every instrument a :class:`repro.cell.ServeCell`
exports, so dashboards, tests and the CI soak read a stable schema
instead of grepping call sites.  All instruments live on an ordinary
:class:`~repro.telemetry.metrics.Registry` (get-or-create semantics —
building the bundle twice on one registry returns the same instruments)
and export through the registry's usual Prometheus/JSON paths.

Counters end in ``_total``; admission decisions carry a ``decision``
label so one metric name covers admitted / degraded / rejected lanes.

The span names of the stream hop live here too: ``StreamLanes.hop``
opens ``cell.hop`` (args: ``seq``, the hop index) around four children,
one per host phase of a hop, and adds each phase's wall time to
``cell_hop_phase_seconds_total{phase=...}``:

* ``copy_in``  -- the host chunk to the device (``jnp.asarray``);
* ``dispatch`` -- the live operand tree and the enqueue of the hop's
  programs;
* ``wait``     -- blocking until the hop's events are computed;
* ``copy_out`` -- the end of the one transfer of the packed events and
  logits, started at dispatch, and their unpacking into host numpy.

``cell_hop_fetches_total`` counts the device-to-host transfers the hops
issued (one per hop on one device), and
``cell_hop_transfer_bytes_total{direction="out"}`` the bytes they moved.
"""

from __future__ import annotations

import dataclasses

from repro.telemetry.metrics import Counter, Gauge, Histogram, Registry

HOP_SPAN = "cell.hop"
HOP_PHASES = ("copy_in", "dispatch", "wait", "copy_out")
HOP_PHASE_SPANS = tuple(f"{HOP_SPAN}.{p}" for p in HOP_PHASES)


@dataclasses.dataclass
class CellMetrics:
    """Every instrument of one serving cell (see module docstring)."""

    # lane lifecycle (streams and LM request slots alike)
    joins: Counter            # cell_lane_joins_total
    evictions: Counter        # cell_lane_evictions_total
    occupancy: Gauge          # cell_lane_occupancy (active / slots)

    # admission control (cell.admission)
    admitted: Counter         # cell_admission_total{decision="admit"}
    degraded: Counter         # cell_admission_total{decision="degrade"}
    rejected: Counter         # cell_admission_total{decision="reject"}
    queue_depth: Gauge        # cell_queue_depth

    # hop/token flow
    hops: Counter             # cell_hops_total (per-lane hops ingested)
    dropped_hops: Counter     # cell_dropped_hops_total (MUST stay 0)
    tokens: Counter           # cell_tokens_total (LM tokens decoded)
    prefill_tokens: Counter   # cell_prefill_tokens_total (joined prompts)
    prefill_positions: Counter  # cell_prefill_positions_total (computed)
    hop_ms: Histogram         # cell_hop_latency_ms
    hop_phase_s: dict         # phase -> cell_hop_phase_seconds_total{phase}
    hop_bytes_in: Counter     # cell_hop_transfer_bytes_total{direction="in"}
    hop_bytes_out: Counter    # ...{direction="out"}
    hop_fetches: Counter      # cell_hop_fetches_total (device-to-host)
    decode_ms: Histogram      # cell_decode_latency_ms
    prefill_ms: Histogram     # cell_prefill_latency_ms
    latency_budget: Gauge     # cell_latency_budget_ms (SLO; 0 = unset)

    # checkpoint hot-swap (cell.hotswap)
    swaps: Counter            # cell_swaps_total
    swap_failures: Counter    # cell_swap_failures_total (parity gate)
    swap_ms: Histogram        # cell_swap_latency_ms (load+warm+verify+swap)
    engine_generation: Gauge  # cell_engine_generation


def make_cell_metrics(registry: Registry) -> CellMetrics:
    """Register (or fetch) the full ``cell_*`` instrument set."""
    adm = "admission decisions for offered lanes"
    phase = "stream hop wall time by host phase"
    xfer = "bytes a stream hop moves between host and device"
    return CellMetrics(
        joins=registry.counter("cell_lane_joins_total",
                               "lanes joined into the batch in flight"),
        evictions=registry.counter("cell_lane_evictions_total",
                                   "lanes evicted (EOS / stream end)"),
        occupancy=registry.gauge("cell_lane_occupancy",
                                 "active lanes / batch slots"),
        admitted=registry.counter("cell_admission_total", adm,
                                  labels={"decision": "admit"}),
        degraded=registry.counter("cell_admission_total", adm,
                                  labels={"decision": "degrade"}),
        rejected=registry.counter("cell_admission_total", adm,
                                  labels={"decision": "reject"}),
        queue_depth=registry.gauge("cell_queue_depth",
                                   "lanes waiting for a slot"),
        hops=registry.counter("cell_hops_total",
                              "per-lane stream hops ingested"),
        dropped_hops=registry.counter(
            "cell_dropped_hops_total",
            "hops lost to churn/swap (the soak asserts 0)"),
        tokens=registry.counter("cell_tokens_total", "LM tokens decoded"),
        prefill_tokens=registry.counter("cell_prefill_tokens_total",
                                        "prompt tokens prefilled at join"),
        prefill_positions=registry.counter(
            "cell_prefill_positions_total",
            "positions the join programs computed: joiners x join width"),
        hop_ms=registry.histogram("cell_hop_latency_ms",
                                  "stream hop wall time", unit="ms"),
        hop_phase_s={p: registry.counter("cell_hop_phase_seconds_total",
                                         phase, labels={"phase": p})
                     for p in HOP_PHASES},
        hop_bytes_in=registry.counter("cell_hop_transfer_bytes_total", xfer,
                                      labels={"direction": "in"}),
        hop_bytes_out=registry.counter("cell_hop_transfer_bytes_total",
                                       xfer, labels={"direction": "out"}),
        hop_fetches=registry.counter(
            "cell_hop_fetches_total",
            "device-to-host transfers stream hops issued"),
        decode_ms=registry.histogram("cell_decode_latency_ms",
                                     "LM decode step wall time", unit="ms"),
        prefill_ms=registry.histogram("cell_prefill_latency_ms",
                                      "LM join prefill wall time",
                                      unit="ms"),
        latency_budget=registry.gauge(
            "cell_latency_budget_ms",
            "per-hop latency SLO; the flight recorder burns against "
            "this (0 = no budget set)"),
        swaps=registry.counter("cell_swaps_total",
                               "checkpoint hot-swaps completed"),
        swap_failures=registry.counter(
            "cell_swap_failures_total",
            "hot-swaps rejected by the probe parity gate"),
        swap_ms=registry.histogram(
            "cell_swap_latency_ms",
            "hot-swap load+warm+verify+install wall time", unit="ms"),
        engine_generation=registry.gauge(
            "cell_engine_generation",
            "EngineHandle generation (bumps once per swap)"),
    )
