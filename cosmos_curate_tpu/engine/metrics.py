"""Prometheus gauges for the engine.

Equivalent of xenna's runtime gauges (reference
docs/curator/guides/OBSERVABILITY.md:286-330, ``ray_pipeline_*``): same
panel semantics under a ``pipeline_*`` prefix so the reference's Grafana
dashboard ports with a find/replace. No-op when prometheus_client is absent
or the exporter port is disabled.
"""

from __future__ import annotations

from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


_SINGLETON: "EngineMetrics | None" = None


def get_metrics(port: int | None = None) -> "EngineMetrics":
    """Process-wide singleton: prometheus collectors register globally, so a
    second EngineMetrics in the same process would collide. A port passed
    after the singleton exists still starts the exporter — the device
    pipeline may record dispatches (creating the singleton portless)
    before the runner asks for the HTTP server."""
    global _SINGLETON
    if _SINGLETON is None:
        _SINGLETON = EngineMetrics(port)
    elif port is not None:
        _SINGLETON.ensure_server(port)
    return _SINGLETON


class EngineMetrics:
    def __init__(self, port: int | None = None) -> None:
        self.enabled = False
        try:
            from prometheus_client import Counter, Gauge, Histogram
        except ImportError:
            return
        labels = ["stage"]
        self.actor_count = Gauge("pipeline_actor_count", "workers per stage", labels + ["state"])
        self.input_queue_size = Gauge("pipeline_input_queue_size", "queued tasks", labels)
        self.process_time_total = Counter(
            "pipeline_stage_process_time_total", "sum of process seconds", labels
        )
        self.deserialize_time_total = Counter(
            "pipeline_stage_deserialize_time_total", "sum of deserialize seconds", labels
        )
        self.tasks_total = Counter("pipeline_tasks_processed_total", "tasks out", labels)
        self.errors_total = Counter("pipeline_task_errors_total", "batch errors", labels)
        self.store_bytes = Gauge("pipeline_object_store_bytes", "object store usage", [])
        # Per-dispatch device-pipeline signal (models/device_pipeline.py):
        # gap = device idle between micro-batches. The autoscaler's tuning
        # target is gap ≈ 0 (host prep keeps the device fed); a rising
        # gap/compute ratio on a stage means it needs more CPU prep workers,
        # not more device workers.
        self.dispatches_total = Counter(
            "pipeline_device_dispatches_total", "device micro-batch dispatches", labels
        )
        self.dispatch_gap_total = Counter(
            "pipeline_device_dispatch_gap_seconds_total",
            "device idle between micro-batches", labels,
        )
        self.dispatch_compute_total = Counter(
            "pipeline_device_compute_seconds_total", "device busy seconds", labels
        )
        self.dispatch_h2d_total = Counter(
            "pipeline_device_h2d_seconds_total", "host->device transfer seconds", labels
        )
        self.dispatch_d2h_total = Counter(
            "pipeline_device_d2h_seconds_total", "device->host readback seconds", labels
        )
        # Pipelined-runner flow signal (core/pipelined_runner.py): fraction
        # of the sampling window a stage's worker threads spent inside
        # process_data. ≈1 marks the bottleneck stage (give it workers);
        # ≈0 with a deep input queue downstream means starved/over-
        # provisioned. Queue depth rides the existing
        # pipeline_input_queue_size gauge.
        self.stage_busy_frac = Gauge(
            "pipeline_stage_busy_fraction",
            "worker busy fraction over the last sampling window", labels,
        )
        # Stage-overlap headline (core/pipelined_runner.py): fraction of
        # summed host-stage work hidden behind other stages over the LAST
        # run — 0 = lockstep, →1-max/sum = perfect overlap. Was a
        # bench-only log line; now a scrapeable gauge.
        self.overlap_frac = Gauge(
            "pipeline_overlap_frac",
            "fraction of summed stage busy time hidden by stage overlap "
            "(last completed run)", [],
        )
        # Caption-engine phase breakdown (models/vlm/engine.py via
        # stage_timer.record_caption_phases): seconds per phase per caption
        # stage, plus shared-prefix KV cache traffic. idle rising against
        # prefill+decode means the stage is starving the engine between
        # batches; hits/(hits+misses) ≈ 1 means the prefix cache is doing
        # its job (every request after the first skips the prefix prefill).
        self.caption_phase_total = Counter(
            "caption_phase_seconds_total",
            "caption engine seconds by phase", labels + ["phase"],
        )
        # A request's life inside the engine (CaptionEngine._stamp): seconds
        # requests spent in each interval (queue, prep, row_wait, prefill,
        # decode) and requests past each boundary (taken, ready, admitted,
        # first, finished; dropped). rate(seconds) / rate(requests) of an
        # interval and the boundary that closes it is a request's mean wait.
        self.caption_request_seconds = Counter(
            "caption_request_seconds_total",
            "seconds caption requests spent in each interval of their life",
            labels + ["interval"],
        )
        self.caption_requests = Counter(
            "caption_requests_total",
            "caption requests past each boundary of their life", labels + ["boundary"],
        )
        self.caption_prefix_hits = Counter(
            "caption_prefix_cache_hits_total", "shared-prefix KV cache hits", labels
        )
        self.caption_prefix_misses = Counter(
            "caption_prefix_cache_misses_total",
            "shared-prefix KV cache misses (builds)", labels,
        )
        self.caption_prefix_saved = Counter(
            "caption_prefix_tokens_saved_total",
            "prefill tokens skipped via shared-prefix hits", labels,
        )
        # Paged-KV + cross-job signals (models/vlm/engine.py block pool):
        # pool occupancy vs capacity is the admission headroom;
        # prefix_block_refs climbing with cow_copies ~0 means prefixes are
        # block-aligned and served copy-free; interleaved_steps > 0 means
        # several owners (stages/jobs) are decoding in ONE batch.
        self.caption_kv_blocks_used = Gauge(
            "caption_kv_blocks_used", "KV pool blocks in use", labels
        )
        self.caption_kv_blocks_total = Gauge(
            "caption_kv_blocks_total", "KV pool block capacity", labels
        )
        self.caption_prefix_block_refs = Counter(
            "caption_prefix_block_refs_total",
            "shared-prefix blocks referenced copy-free by admitted requests",
            labels,
        )
        self.caption_kv_cow = Counter(
            "caption_kv_cow_copies_total",
            "copy-on-write duplications of shared prefix tail blocks", labels,
        )
        self.caption_interleaved_steps = Counter(
            "caption_interleaved_steps_total",
            "decode steps whose active slots spanned 2+ owners", labels,
        )
        # Paged-attention path signal (ops/paged_attention.py): decode
        # steps served without a gathered KV working set. Stays 0 only on
        # an engine built with paged_attention="gather" (the reference
        # programs).
        self.caption_paged_kernel_steps = Counter(
            "caption_paged_kernel_steps_total",
            "decode steps served by the paged-attention programs", labels,
        )
        # the stage_timer._CAPTION_COUNT_KEYS that have a counter of their own
        self._caption_counts = {
            "prefix_cache_hits": self.caption_prefix_hits,
            "prefix_cache_misses": self.caption_prefix_misses,
            "prefix_tokens_saved": self.caption_prefix_saved,
            "prefix_block_refs": self.caption_prefix_block_refs,
            "kv_cow_copies": self.caption_kv_cow,
            "interleaved_steps": self.caption_interleaved_steps,
            "paged_kernel_steps": self.caption_paged_kernel_steps,
        }
        # per-owner queue/in-flight gauges for the SHARED engine: which
        # job/stage is occupying or starving the continuous batch
        self.caption_owner_queue = Gauge(
            "caption_owner_queue",
            "caption engine requests per owner by state",
            ["owner", "state"],
        )
        # Cross-host object-plane signal (engine/object_channel.py via
        # stage_timer.record_object_plane): bytes moved between nodes, how
        # long consumers waited for them, and whether push-ahead prefetch
        # hid the transfer. Healthy cross-host pipelining reads as
        # prefetch hits ≈ transfers and wait_seconds{kind="prefetch_hit"}
        # ≈ 0 while bytes_total keeps climbing — transfers overlap compute
        # instead of serializing against it.
        node_labels = ["node"]
        self.object_plane_transfers = Counter(
            "pipeline_object_plane_transfers_total",
            "cross-node segment transfers", node_labels + ["kind"],
        )
        self.object_plane_bytes = Counter(
            "pipeline_object_plane_bytes_total",
            "cross-node bytes moved", node_labels + ["kind"],
        )
        self.object_plane_wait = Counter(
            "pipeline_object_plane_wait_seconds_total",
            "seconds consumers waited on object-plane transfers",
            node_labels + ["kind"],
        )
        self.object_plane_prefetch_hits = Counter(
            "pipeline_object_plane_prefetch_hits_total",
            "batch inputs already local when demanded (push-ahead worked)",
            node_labels,
        )
        self.object_plane_prefetch_misses = Counter(
            "pipeline_object_plane_prefetch_misses_total",
            "batch inputs demand-fetched (no prefetch landed first)",
            node_labels,
        )
        # Corpus-index signal (dedup/corpus_index.py via
        # stage_timer.record_index_ops): vectors entering the persistent
        # index, query traffic, probe fan-out, and time spent on each side.
        # Healthy incremental dedup reads as queries tracking clip flow with
        # query_seconds << what a full re-cluster would cost; probes rising
        # against queries means nprobe (recall) is being bought with extra
        # shard matmuls. skipped_random > 0 flags a run whose embeddings
        # were refused for random-weight provenance.
        self.index_adds = Counter(
            "pipeline_index_adds_total", "vectors added to the corpus index", labels
        )
        self.index_add_seconds = Counter(
            "pipeline_index_add_seconds_total",
            "seconds spent appending/consolidating index fragments", labels,
        )
        self.index_queries = Counter(
            "pipeline_index_queries_total", "index query vectors", labels
        )
        self.index_query_seconds = Counter(
            "pipeline_index_query_seconds_total",
            "seconds spent in index query batches", labels,
        )
        self.index_probes = Counter(
            "pipeline_index_probes_total", "cluster shards probed by queries", labels
        )
        self.index_duplicates = Counter(
            "pipeline_index_duplicates_total",
            "query vectors flagged duplicate of an indexed neighbor", labels,
        )
        self.index_skipped_random = Counter(
            "pipeline_index_skipped_random_total",
            "vectors refused for random-weight provenance", labels,
        )
        # Index-server read path (dedup/index_server.py + /v1/search): the
        # latency SLO histogram (p50/p99 from the buckets), warm-shard-cache
        # byte traffic (hit ratio by BYTES — a fat shard miss hurts more
        # than a tiny one), compaction generations, and search sheds.
        # Healthy serving reads as p99 inside the interactive bucket range,
        # hit bytes >> miss bytes after warmup, and the generation gauge
        # ticking up while latency stays flat (compaction never stalls
        # reads — that is what the snapshots are for).
        self.search_latency = Histogram(
            "search_latency_seconds",
            "similarity-search request latency (submit to results)",
            labels + ["mode"],
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0),
        )
        self.search_requests = Counter(
            "search_requests_total", "similarity-search requests served",
            labels + ["mode"],
        )
        self.search_shed = Counter(
            "search_shed_total",
            "search requests shed with 429 (admission lane over capacity)",
            labels + ["reason"],
        )
        self.index_cache_hit_bytes = Counter(
            "index_cache_hit_bytes_total",
            "shard bytes served from the warm cache", labels,
        )
        self.index_cache_miss_bytes = Counter(
            "index_cache_miss_bytes_total",
            "shard bytes faulted in from storage", labels,
        )
        self.index_cache_evicted_bytes = Counter(
            "index_cache_evicted_bytes_total",
            "shard bytes evicted under the byte budget", labels,
        )
        self.index_compactions = Counter(
            "index_compactions_total", "compaction passes that published", labels,
        )
        self.index_generation = Gauge(
            "index_generation",
            "manifest generation (published by compaction / served by the "
            "index server)", labels,
        )
        # Per-node flow (engine/runner.py metrics tick): workers placed on
        # and CPU units used per connected node — the per-node counterpart
        # of pipeline_actor_count, so a merged dashboard shows which host
        # is starved instead of one flat pool number.
        self.node_workers = Gauge(
            "pipeline_node_workers", "stage workers placed per node", node_labels
        )
        self.node_cpus_used = Gauge(
            "pipeline_node_cpus_used", "CPU units in use per node", node_labels
        )
        # Node-loss fault tolerance (engine/runner.py + remote_plane.py):
        # declared node deaths (heartbeat deadline or link loss), objects
        # re-materialized through lineage reconstruction, and the wall time
        # those re-runs took. Healthy node churn reads as deaths > 0 with
        # reconstructed > 0 and ZERO dead-lettered batches; deaths with no
        # reconstruction means lineage had already expired (or the budget
        # is too tight) and work is dropping instead of recomputing.
        self.node_deaths = Counter(
            "pipeline_node_deaths_total",
            "agents declared dead (heartbeat deadline or link loss)",
            node_labels,
        )
        self.objects_reconstructed = Counter(
            "pipeline_objects_reconstructed_total",
            "lost objects re-materialized via lineage re-execution", labels,
        )
        self.reconstruction_seconds = Counter(
            "pipeline_reconstruction_seconds_total",
            "wall seconds spent re-executing producer batches", [],
        )
        # Job-service lifecycle (service/app.py): transitions per tenant,
        # current per-state counts, queue wait, and sheds. shed_total rising
        # under `tenant_queue_full` is a noisy tenant hitting its quota
        # (working as intended); rising under `queue_full` means the whole
        # service is over capacity — scale out or raise the dispatcher cap.
        self.service_transitions = Counter(
            "service_jobs_total", "job state transitions", ["tenant", "state"]
        )
        # NB: "service_jobs" itself is taken — prometheus_client registers
        # a Counter's base name (service_jobs_total → service_jobs)
        self.service_jobs_state = Gauge(
            "service_jobs_current", "current jobs per state", ["state"]
        )
        self.service_queue_depth = Gauge(
            "service_queue_depth", "queued jobs per lane", ["lane"]
        )
        self.service_queue_wait = Counter(
            "service_queue_wait_seconds_total",
            "summed pending->running wait", ["lane"],
        )
        self.service_dispatches = Counter(
            "service_dispatches_total",
            "jobs dispatched (divide queue_wait by this for mean wait)", ["lane"],
        )
        self.service_shed = Counter(
            "service_shed_total", "admissions shed with 429", ["tenant", "reason"]
        )
        # Live ops plane (observability/anomaly.py + service SLOs): detector
        # verdicts per stage and kind, and per-tenant SLO breaches. A flat
        # zero anomaly rate on a healthy fleet is the baseline; any nonzero
        # stuck_batch/starved_stage rate is an operator page, and
        # slo_breaches rising for one tenant with flat queue depth means
        # that tenant's target is mis-sized, not the service.
        self.anomalies_total = Counter(
            "pipeline_anomalies_total",
            "stall/anomaly detector verdicts", labels + ["kind"],
        )
        self.slo_breaches = Counter(
            "service_slo_breaches_total",
            "per-tenant SLO breaches (queue_wait, run_duration, success_rate)",
            ["tenant", "kind"],
        )
        self._server_started = False
        self.enabled = True
        if port is not None:
            self.ensure_server(port)

    def ensure_server(self, port: int) -> None:
        """Start the exporter once; safe to call after construction."""
        if not self.enabled or self._server_started:
            return
        from prometheus_client import start_http_server

        try:
            start_http_server(port)
            self._server_started = True
            logger.info("prometheus metrics on :%d", port)
        except OSError as e:
            logger.warning("metrics server failed to start: %s", e)

    def observe_result(self, stage: str, process_s: float, deser_s: float, n_out: int) -> None:
        if not self.enabled:
            return
        self.process_time_total.labels(stage).inc(process_s)
        self.deserialize_time_total.labels(stage).inc(deser_s)
        self.tasks_total.labels(stage).inc(n_out)

    def observe_error(self, stage: str) -> None:
        if self.enabled:
            self.errors_total.labels(stage).inc()

    def observe_dispatch(
        self, stage: str, *, gap_s: float, compute_s: float = 0.0,
        h2d_s: float = 0.0, d2h_s: float = 0.0,
    ) -> None:
        if not self.enabled:
            return
        self.dispatches_total.labels(stage).inc()
        self.dispatch_gap_total.labels(stage).inc(max(gap_s, 0.0))
        self.dispatch_compute_total.labels(stage).inc(max(compute_s, 0.0))
        self.dispatch_h2d_total.labels(stage).inc(max(h2d_s, 0.0))
        self.dispatch_d2h_total.labels(stage).inc(max(d2h_s, 0.0))

    def observe_dispatch_aggregate(self, stage: str, agg: dict) -> None:
        """Fold a worker-dumped dispatch AGGREGATE (stage_timer dump schema)
        into the counters — the finalize-time path that completes the
        ``pipeline_device_*`` series for spawned engine workers, which have
        no exporter of their own."""
        if not self.enabled:
            return
        self.dispatches_total.labels(stage).inc(max(0, int(agg.get("dispatches", 0))))
        self.dispatch_gap_total.labels(stage).inc(max(0.0, float(agg.get("gap_s", 0.0))))
        self.dispatch_compute_total.labels(stage).inc(
            max(0.0, float(agg.get("compute_s", 0.0)))
        )
        self.dispatch_h2d_total.labels(stage).inc(max(0.0, float(agg.get("h2d_s", 0.0))))
        self.dispatch_d2h_total.labels(stage).inc(max(0.0, float(agg.get("d2h_s", 0.0))))

    def observe_caption_phases(self, stage: str, phases: dict) -> None:
        """Fold one caption-engine drive's phase/cache deltas (the
        stage_timer.record_caption_phases schema) into the counters."""
        if not self.enabled:
            return
        for phase in ("prep_s", "vision_encode_s", "prefill_s", "decode_s", "idle_s"):
            self.caption_phase_total.labels(stage, phase[:-2]).inc(
                max(0.0, float(phases.get(phase, 0.0)))
            )
        for interval in ("queue", "prep", "row_wait", "prefill", "decode"):
            self.caption_request_seconds.labels(stage, interval).inc(
                max(0.0, float(phases.get(f"request_{interval}_s", 0.0)))
            )
        for boundary in ("taken", "ready", "admitted", "first", "finished", "dropped"):
            self.caption_requests.labels(stage, boundary).inc(
                max(0, int(phases.get(f"request_{boundary}_n", 0)))
            )
        for key, counter in self._caption_counts.items():
            counter.labels(stage).inc(max(0, int(phases.get(key, 0))))
        if "kv_blocks_used" in phases:
            self.caption_kv_blocks_used.labels(stage).set(
                max(0, int(phases["kv_blocks_used"]))
            )
        if "kv_blocks_total" in phases:
            self.caption_kv_blocks_total.labels(stage).set(
                max(0, int(phases["kv_blocks_total"]))
            )

    def observe_caption_owners(self, owners: dict) -> None:
        """Set the per-owner queue gauges from ``CaptionEngine.owner_stats``
        (cross-job continuous batching: who occupies the shared engine).
        Owners absent from the snapshot have their gauge children REMOVED —
        owner tags are per-stage-instance, so a long-lived service would
        otherwise accumulate stale series forever (and a stage that died
        mid-drive would pin a nonzero ``inflight`` at its last value)."""
        if not self.enabled:
            return
        seen = getattr(self, "_caption_owner_seen", None)
        if seen is None:
            seen = self._caption_owner_seen = set()
        for owner, stats in owners.items():
            seen.add(str(owner))
            for state in ("waiting", "ready", "inflight"):
                self.caption_owner_queue.labels(owner, state).set(
                    max(0, int(stats.get(state, 0)))
                )
        for owner in [o for o in seen if o not in owners]:
            seen.discard(owner)
            for state in ("waiting", "ready", "inflight"):
                try:
                    self.caption_owner_queue.remove(owner, state)
                except KeyError:
                    pass

    def observe_index(self, stage: str, deltas: dict) -> None:
        """Fold one corpus-index operation's deltas (the
        stage_timer.INDEX_OP_KEYS schema) into the counters."""
        if not self.enabled:
            return
        for counter, key in (
            (self.index_adds, "adds"),
            (self.index_add_seconds, "add_s"),
            (self.index_queries, "queries"),
            (self.index_query_seconds, "query_s"),
            (self.index_probes, "probes"),
            (self.index_duplicates, "duplicates"),
            (self.index_skipped_random, "skipped_random"),
        ):
            counter.labels(stage).inc(max(0.0, float(deltas.get(key, 0))))

    def observe_search(
        self, name: str, mode: str, latency_s: float | None, deltas: dict
    ) -> None:
        """Fold one search-serving delta set (stage_timer.SEARCH_KEYS
        schema) into the ``search_*`` / ``index_cache_*`` series."""
        if not self.enabled:
            return
        if latency_s is not None:
            self.search_latency.labels(name, mode).observe(max(0.0, float(latency_s)))
            self.search_requests.labels(name, mode).inc()
        for counter, key in (
            (self.index_cache_hit_bytes, "cache_hit_bytes"),
            (self.index_cache_miss_bytes, "cache_miss_bytes"),
            (self.index_cache_evicted_bytes, "cache_evicted_bytes"),
        ):
            v = float(deltas.get(key, 0))
            if v > 0:
                counter.labels(name).inc(v)

    def observe_search_shed(self, name: str, reason: str) -> None:
        if self.enabled:
            self.search_shed.labels(name, reason).inc()

    def observe_compaction(self, name: str, generation: int) -> None:
        if not self.enabled:
            return
        self.index_compactions.labels(name).inc()
        self.index_generation.labels(name).set(int(generation))

    def set_index_generation(self, name: str, generation: int) -> None:
        if self.enabled:
            self.index_generation.labels(name).set(int(generation))

    def observe_object_plane(self, node: str, deltas: dict) -> None:
        """Fold one object-plane delta set (stage_timer.OBJECT_PLANE_KEYS
        schema) into the counters under ``node``."""
        if not self.enabled:
            return
        for kind, (n_key, b_key, w_key) in {
            "fetch": ("fetches", "fetch_bytes", "fetch_wait_s"),
            "prefetch": ("prefetches", "prefetch_bytes", "prefetch_transfer_s"),
            "store_read": ("store_reads", "store_read_bytes", "store_read_wait_s"),
        }.items():
            self.object_plane_transfers.labels(node, kind).inc(
                max(0.0, float(deltas.get(n_key, 0)))
            )
            self.object_plane_bytes.labels(node, kind).inc(
                max(0.0, float(deltas.get(b_key, 0)))
            )
            self.object_plane_wait.labels(node, kind).inc(
                max(0.0, float(deltas.get(w_key, 0.0)))
            )
        self.object_plane_wait.labels(node, "prefetch_hit").inc(
            max(0.0, float(deltas.get("prefetch_hit_wait_s", 0.0)))
        )
        self.object_plane_prefetch_hits.labels(node).inc(
            max(0.0, float(deltas.get("prefetch_hits", 0)))
        )
        self.object_plane_prefetch_misses.labels(node).inc(
            max(0.0, float(deltas.get("prefetch_misses", 0)))
        )

    def set_node_state(self, node: str, workers: int, cpus_used: float) -> None:
        if self.enabled:
            self.node_workers.labels(node).set(workers)
            self.node_cpus_used.labels(node).set(cpus_used)

    def observe_node_death(self, node: str) -> None:
        if self.enabled:
            self.node_deaths.labels(node).inc()

    def observe_reconstruction(self, stage: str, objects: int, seconds: float) -> None:
        if not self.enabled:
            return
        self.objects_reconstructed.labels(stage).inc(max(0, int(objects)))
        self.reconstruction_seconds.inc(max(0.0, float(seconds)))

    def set_overlap_frac(self, frac: float) -> None:
        if self.enabled:
            self.overlap_frac.set(min(max(frac, 0.0), 1.0))

    def set_stage_busy(self, stage: str, frac: float) -> None:
        if self.enabled:
            self.stage_busy_frac.labels(stage).set(min(max(frac, 0.0), 1.0))

    def set_pool_state(self, stage: str, ready: int, pending: int, queued: int) -> None:
        if not self.enabled:
            return
        self.actor_count.labels(stage, "ready").set(ready)
        self.actor_count.labels(stage, "pending").set(pending)
        self.input_queue_size.labels(stage).set(queued)

    def set_store_bytes(self, used: int) -> None:
        if self.enabled:
            self.store_bytes.set(used)

    def observe_service_transition(self, tenant: str, state: str) -> None:
        if self.enabled:
            self.service_transitions.labels(tenant, state).inc()

    def set_service_states(self, counts: dict) -> None:
        """``counts``: state -> current job count (all known states, so a
        state that empties out reads 0 instead of its stale last value)."""
        if not self.enabled:
            return
        for state, n in counts.items():
            self.service_jobs_state.labels(state).set(int(n))

    def set_service_queue_depth(self, lane: str, depth: int) -> None:
        if self.enabled:
            self.service_queue_depth.labels(lane).set(int(depth))

    def observe_service_dispatch(self, lane: str, wait_s: float) -> None:
        if not self.enabled:
            return
        self.service_dispatches.labels(lane).inc()
        self.service_queue_wait.labels(lane).inc(max(0.0, wait_s))

    def observe_service_shed(self, tenant: str, reason: str) -> None:
        if self.enabled:
            self.service_shed.labels(tenant, reason).inc()

    def observe_anomaly(self, stage: str, kind: str) -> None:
        if self.enabled:
            self.anomalies_total.labels(stage, kind).inc()

    def observe_slo_breach(self, tenant: str, kind: str) -> None:
        if self.enabled:
            self.slo_breaches.labels(tenant, kind).inc()
