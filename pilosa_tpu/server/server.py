"""Server runtime: lifecycle wiring of holder, API, HTTP, background loops.

Reference: server.go (Server, Open, anti-entropy ticker, receiveMessage,
monitorRuntime) + server/server.go (Command wiring). Single-node by
default; passing seeds in the config attaches the cluster layer
(pilosa_tpu.parallel.cluster) which swaps in scatter-gather routers and
the /internal/* data-plane routes.
"""

from __future__ import annotations

import os
import threading

from pilosa_tpu.core import Holder
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import HTTPServer
from pilosa_tpu.utils.config import Config


class Server:
    # seconds a query/import that arrives while open() is still attaching
    # the device waits for the executor to be bound before 503 +
    # Retry-After (the listener serves before the attach finishes)
    ATTACH_WAIT_S = 60.0

    def __init__(self, config: Config | None = None):
        self.config = config or Config()
        from pilosa_tpu.utils.stats import make_stats

        self.stats = make_stats(
            self.config.metric_service, self.config.statsd_host
        )
        from pilosa_tpu.utils.log import Logger

        self.logger = Logger(
            os.path.expanduser(self.config.log_path)
            if self.config.log_path
            else None
        )
        # WAL acknowledgement policy (docs/durability.md) is process-
        # global — set it before the holder exists so even open()-time
        # repairs write under the configured mode
        from pilosa_tpu.utils import durable

        durable.set_wal_fsync_mode(self.config.wal_fsync_mode)
        self.holder = Holder(
            os.path.expanduser(self.config.data_dir),
            compaction_workers=self.config.compaction_workers,
            load_workers=self.config.holder_load_workers,
            load_min_fragments=self.config.holder_load_min_fragments,
            stats=self.stats,
        )
        self.cluster = None
        # deterministic fault injection (docs/fault-tolerance.md):
        # always constructed — zero cost unarmed — so the /debug/faults
        # route can arm rules on a live node; the cluster's outgoing
        # client chain consults this same instance
        from pilosa_tpu.parallel.faultinject import FaultInjector, FSFaultInjector

        self.fault_injector = FaultInjector.from_config(self.config)
        # filesystem fault layer (docs/durability.md): installed process-
        # wide in open() ONLY when rules are armed — the durable write
        # protocol consults the hook at every primitive, and the chaos
        # suite needs the faults to land exactly where real disk faults
        # would. Uninstalled in close().
        self.fs_fault_injector = FSFaultInjector.from_config(self.config)
        # first-class device stack budget (docs/device-residency.md):
        # the config knob wins over the legacy PILOSA_TPU_STACK_BUDGET
        # env resolution; 0 leaves auto-resolution in place
        from pilosa_tpu.executor import compile as query_compile

        # unconditional: a 0 (auto) config must CLEAR any override a
        # previous Server in this process installed, or its budget
        # would silently leak into this one's auto-resolution
        query_compile.set_stack_budget(
            self.config.device_stack_budget_bytes or None
        )
        # per-call host/device cost router (docs/query-routing.md),
        # seeded from config; the SAME router instance survives the
        # late mesh attach so its calibration carries over
        from pilosa_tpu.executor.router import QueryRouter

        router = QueryRouter(
            mode=self.config.route_mode,
            stats=self.stats,
            dispatch_seed_s=self.config.route_dispatch_ms / 1e3,
            readback_seed_s=self.config.route_readback_ms / 1e3,
            device_wps=self.config.route_device_words_per_s,
            crossover_words=self.config.route_crossover_words,
            mesh_dispatch_seed_s=self.config.route_mesh_dispatch_ms / 1e3,
            mesh_readback_seed_s=self.config.route_mesh_readback_ms / 1e3,
            audit_enabled=self.config.router_audit_enabled,
        )
        # mesh_ctx=None here: MeshContext.auto() initializes the full JAX
        # backend (seconds on an accelerator) — that must not block
        # Server() construction; open() attaches the device AFTER the
        # listener is serving (see open()'s ordering rationale)
        self.api = API(
            self.holder,
            stats=self.stats,
            mesh_ctx=None,
            max_writes=self.config.max_writes_per_request,
            router=router,
            batch_mode=self.config.batch_mode,
            batch_window_us=self.config.batch_window_us,
            batch_max_queries=self.config.batch_max_queries,
        )
        self.http: HTTPServer | None = None
        self.profiler = None
        self.diagnostics = None
        self._anti_entropy_timer: threading.Timer | None = None
        self._closed = False
        self._mesh_attach_thread: threading.Thread | None = None
        # set when the attach thread has finished (executor bound, or
        # the backend failed — see _attach_error). Starts UNSET so the
        # gate holds queries from the instant the listener serves: the
        # attach thread is only created later in open() (after the
        # multihost join), and a query must not race the executor swap.
        self._mesh_ready = threading.Event()
        self._attach_error: Exception | None = None

    def open(self) -> None:
        """holder load → HTTP up → cluster join → background loops
        (reference: Server.Open). The listener must serve BEFORE the
        cluster join: the listener binds in its constructor, so a peer
        that probed a bound-but-not-serving node would hang in the accept
        backlog for the full client timeout instead of getting an instant
        connection-refused — concurrent cold starts then stack 30s
        timeouts on each other."""
        if self.fs_fault_injector.armed:
            # before holder.open(): crash-recovery rehearsals target the
            # load path (snapshot reads, torn-tail truncation) too
            from pilosa_tpu.utils import durable

            durable.install_fs_hook(self.fs_fault_injector)
        self.holder.open()
        self.http = HTTPServer(
            (self.config.host, self.config.port), self.api, stats=self.stats
        )
        # admission/backpressure knobs (docs/serving.md)
        self.http.max_connections = self.config.max_connections
        self.http.admission_queue_depth = self.config.admission_queue_depth
        self.http.keepalive_idle_s = self.config.keepalive_idle_s
        self.http.request_read_timeout_s = self.config.request_read_timeout_s
        self.http.worker_threads = self.config.http_worker_threads
        # write-class backpressure tied to compaction debt
        # (docs/durability.md): past the limit, imports get 429 +
        # Retry-After instead of growing ops logs without bound
        self.http.compaction_max_debt = self.config.compaction_max_debt
        self.http.compaction_debt = self.holder.compactor.debt
        if self.config.tls_certificate:
            # serve HTTPS (reference: tls.certificate/tls.key). The context
            # is handed to the listener, whose loop runs each accepted
            # connection's handshake, bounded by request-read-timeout-s.
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(
                os.path.expanduser(self.config.tls_certificate),
                os.path.expanduser(self.config.tls_key) or None,
            )
            self.http.ssl_context = ctx
        self.http.node_id = self.config.node_id
        # config-sized flight recorder (docs/observability.md) replaces
        # the listener's default one; wired to this server's logger so
        # the structured slow-query line lands in the configured sink
        from pilosa_tpu.utils.flightrec import FlightRecorder

        self.http.flightrec = FlightRecorder(
            capacity=self.config.flightrec_entries,
            min_latency_s=self.config.flightrec_min_ms / 1e3,
            stats=self.stats,
            log=self.logger.log,
            enabled=self.config.flightrec_enabled,
        )
        # config-sized workload-intelligence plane (docs/workload.md)
        # replaces the listener's default one: capture ring + durable
        # spill + heavy-hitter sketch + SLO engine. slo-targets parse
        # failures raise HERE, at boot — a typo'd objective discovered
        # when the dashboard stays empty would defeat the point.
        from pilosa_tpu.utils.workload import WorkloadPlane

        self.http.workload = WorkloadPlane(
            enabled=self.config.workload_capture_enabled,
            capacity=self.config.workload_capture_entries,
            sample_rate=self.config.workload_sample_rate,
            top_k=self.config.workload_top_k,
            capture_path=(
                os.path.expanduser(self.config.workload_capture_path)
                if self.config.workload_capture_path
                else None
            ),
            spill_max_bytes=self.config.workload_spill_max_bytes,
            spill_max_age_s=self.config.workload_spill_max_age_s,
            spill_segments=self.config.workload_spill_segments,
            slo_targets=self.config.slo_targets,
            stats=self.stats,
            log=self.logger.log,
        )
        # config-sized result cache (docs/result-cache.md) replaces the
        # listener's default one; the cache's per-entry byte cap feeds
        # the workload plane's cachability estimator so repeats of
        # never-admittable giant results stop counting as servable
        from pilosa_tpu.utils.resultcache import ResultCache

        self.http.result_cache = ResultCache(
            max_bytes=self.config.result_cache_bytes,
            min_cost_ms=self.config.result_cache_min_cost_ms,
            mode=self.config.result_cache_mode,
            stats=self.stats,
        )
        self.api.result_cache = self.http.result_cache
        self.http.workload.cache_byte_cap = (
            self.http.result_cache.entry_byte_cap
        )
        # continuous profiling + saturation plane (docs/profiling.md):
        # the config-sized sampler replaces the listener's None slot and
        # STARTS here — a flame graph of the last minute is one curl
        # away for the life of the process; the saturation monitor gets
        # the module-level metrics sink (hot locks are constructed deep
        # inside core/executor where no client is in scope) and its GIL
        # probe thread
        from pilosa_tpu.utils import saturation, xlaevents
        from pilosa_tpu.utils.profiler import SamplingProfiler

        saturation.set_stats(self.stats)
        # compile counter with its site (docs/observability.md): jax
        # reports each compile on the thread that asked for it
        xlaevents.set_stats(self.stats)
        self.profiler = SamplingProfiler(
            hz=self.config.profiler_hz,
            segment_s=self.config.profiler_segment_s,
            segments=self.config.profiler_segments,
            stats=self.stats,
            enabled=self.config.profiler_enabled,
        )
        self.profiler.start()
        self.http.profiler = self.profiler
        self.http.saturation = saturation.SaturationMonitor(
            stats=self.stats,
            enabled=self.config.saturation_probes_enabled,
        )
        self.http.saturation.start()
        if self.config.access_log_format not in ("", "json"):
            raise ValueError(
                "access-log-format must be \"\" or \"json\", got "
                f"{self.config.access_log_format!r}"
            )
        self.http.access_log_json = self.config.access_log_format == "json"
        self.http.long_query_time = self.config.long_query_time
        self.http.query_timeout_ms = self.config.query_timeout_ms
        self.http.fault_injector = self.fault_injector
        self.http.fs_fault_injector = self.fs_fault_injector
        self.http.log = self.logger.log
        self.http.gate = self._attach_gate
        # multi-process fleet state (docs/multiprocess.md): a supervised
        # child reads the supervisor's state file to serve the stitched
        # GET /debug/processes view
        self.http.supervisor_state_path = (
            os.path.expanduser(self.config.supervisor_state)
            if self.config.supervisor_state
            else None
        )
        if self.config.seeds or self.config.coordinator:
            from pilosa_tpu.parallel.cluster import Cluster

            self.cluster = Cluster(self)
            self.api.cluster = self.cluster
            # routes/routers must be live before the first request or a
            # client could be silently served local-only (and peers 404)
            self.cluster.attach()
        self.http.serve_background()
        if self.config.coordinator_address:
            # join the static jax.distributed process group BEFORE any
            # other backend use (reference analogue: gossip join); the
            # listener is already serving so peers' health probes succeed
            # while this blocks on the coordinator barrier
            from pilosa_tpu.parallel import multihost

            multihost.init_distributed(
                self.config.coordinator_address,
                self.config.num_processes or None,
                self.config.process_id if self.config.process_id >= 0 else None,
            )
        # Device bring-up OFF-THREAD so backend init (seconds on an
        # accelerator) overlaps the cluster join below; open() waits for
        # it at the end and re-raises what it raised. attach_mesh
        # rebinds whole objects, so a query admitted meanwhile sees
        # either the old or the new executor.
        t = threading.Thread(
            target=self._attach_device, daemon=True, name="mesh-attach",
        )
        t.start()
        self._mesh_attach_thread = t
        if self.cluster is not None:
            self.cluster.join()
        # multi-process serving (docs/multiprocess.md): join the shared
        # public port only NOW — after the cluster join has completed —
        # so the kernel (reuseport) or the parent (fd-pass) never routes
        # a public connection to a child that cannot serve its shard
        # subset yet (readiness gating before the port is announced)
        if self.config.shared_bind:
            host, _, port = self.config.shared_bind.rpartition(":")
            self.http.add_shared_listener(host, int(port))
            self.logger.log(
                "shared public listener bound via SO_REUSEPORT on "
                f"{self.config.shared_bind}"
            )
        if self.config.fd_pass_socket:
            self.http.add_fd_listener(
                os.path.expanduser(self.config.fd_pass_socket)
            )
            self.logger.log(
                "adopting accept-and-pass connections from "
                f"{self.config.fd_pass_socket}"
            )
        self._schedule_anti_entropy()
        from pilosa_tpu.server.diagnostics import DiagnosticsCollector

        self.diagnostics = DiagnosticsCollector(self)
        self.api.diagnostics = self.diagnostics
        self.diagnostics.open()
        # a backend that failed to initialize is an error, not a mode:
        # the caller closes the server and the process exits with it
        self.wait_mesh()

    def _attach_device(self) -> None:
        """Initialize the JAX backend IN THIS PROCESS (the chip is local
        and belongs to one process — nobody else can be asked about it)
        and bind the mesh executor. Whatever this raises is recorded for
        wait_mesh()/open() to re-raise."""
        try:
            import jax

            jax.local_devices()
            if self.config.mesh_enabled and not self._closed:
                self.api.attach_mesh(self._make_mesh_context())
        except Exception as e:  # pilosa: allow(broad-except) — backend
            # init raises backend-specific errors; wait_mesh re-raises
            self._attach_error = e
        finally:
            self._mesh_ready.set()

    def _attach_gate(self, wait: bool = True) -> bool:
        """Hold query/import dispatch until the attach thread has bound
        the executor: the listener serves from early in open(), and a
        query admitted before the swap would build its stacks on an
        executor that is about to be replaced. With ``wait``, blocks up
        to ATTACH_WAIT_S; past that the HTTP layer serves 503 +
        Retry-After. ``wait=False`` is for the internal fan-out route,
        whose caller's RPC timeout (30s) is shorter than the wait — it
        must fail fast and let the coordinator retry."""
        return self._mesh_ready.wait(self.ATTACH_WAIT_S if wait else 0)

    def wait_mesh(self, timeout: float | None = None) -> bool:
        """Block until the off-thread device attach finishes; re-raises
        what it raised. True when the attach is done, False on timeout."""
        if not self._mesh_ready.wait(timeout):
            return False
        if self._attach_error is not None:
            raise self._attach_error
        return True

    def _make_mesh_context(self):
        """Serving mesh: always over this process's LOCAL devices — even
        in a multi-host deployment. A global (cross-process) mesh program
        is a collective: every process must enter it in lockstep, and the
        HTTP query path is driven by whichever node a client happens to
        hit, so attaching a global mesh here would hang the first query
        in a DCN psum waiting for peers that never dispatch it. Cross-
        host queries therefore scatter-gather through parallel.cluster
        (each node reducing over its local mesh), while the global-mesh
        data plane (MeshContext(multihost=True) + MeshQueryEngine) is for
        symmetric SPMD drivers — every process running the same program —
        as in tests/test_multihost.py's two-process Count."""
        from pilosa_tpu.parallel.mesh import MeshContext

        return MeshContext.auto(words_axis=self.config.mesh_words_axis)

    def _schedule_anti_entropy(self) -> None:
        interval = self.config.anti_entropy_interval
        if interval <= 0 or self._closed:
            return

        def tick():
            try:
                if self.cluster is not None:
                    self.cluster.sync_holder()
            finally:
                self._schedule_anti_entropy()

        self._anti_entropy_timer = threading.Timer(interval, tick)
        self._anti_entropy_timer.daemon = True
        self._anti_entropy_timer.name = "anti-entropy"
        self._anti_entropy_timer.start()

    @property
    def port(self) -> int:
        """Actual bound port (useful when config requested :0)."""
        return self.http.server_address[1] if self.http else self.config.port

    @property
    def uri(self) -> str:
        return f"{self.config.scheme}://{self.config.host}:{self.port}"

    def close(self) -> None:
        """Stop the listener, then close everything under it.  The
        listener goes first and raises while a request is still running
        in a worker (``EventHTTPServer.shutdown``): nothing else has
        been closed then, so the node is whole but for its listener,
        and a later ``close()`` goes through once the worker has
        returned."""
        if self.http is not None:
            self.http.shutdown()
        self._closed = True
        # reap the attach thread (bounded): it must not swap an
        # executor into an API whose holder is closing
        t = self._mesh_attach_thread
        if t is not None:
            t.join(timeout=10.0)
            if t.is_alive():
                # it checks _closed before it binds; what is left is a
                # backend that hangs in its own start-up
                self.logger.log(
                    "close error: device attach still running after 10s"
                )
        if self.diagnostics is not None:
            self.diagnostics.close()
        if self._anti_entropy_timer is not None:
            self._anti_entropy_timer.cancel()
        if self.cluster is not None:
            self.cluster.close()
        self.api.scheduler.close()
        if self.profiler is not None:
            self.profiler.stop()
        if self.http is not None:
            self.http.saturation.stop()
            # flush the open workload spill segment: a capture cut off
            # mid-segment replays short
            self.http.workload.close()
            self.http.server_close()
        self.stats.close()
        self.holder.close()
        if self.fs_fault_injector.armed:
            from pilosa_tpu.utils import durable

            durable.install_fs_hook(None)
        self.logger.close()
