"""REST control plane.

Re-design of ``src/runtime/ctrl_port.rs:96-199`` (axum server on a dedicated thread): an
aiohttp server on its own thread + event loop, exposing the same four endpoint families:

  GET  /api/fg/                                   → list of flowgraph ids
  GET  /api/fg/{fg}/                              → FlowgraphDescription
  GET  /api/fg/{fg}/block/{blk}/                  → BlockDescription
  GET  /api/fg/{fg}/block/{blk}/call/{handler}/   → call with Pmt::Null
  POST /api/fg/{fg}/block/{blk}/call/{handler}/   → call with JSON-Pmt body

plus the telemetry plane (docs/observability.md):

  GET  /metrics                → Prometheus text exposition: registry counters
                                 + per-block families for every live flowgraph
  GET  /api/fg/{fg}/trace/     → drain the span ring as Chrome trace-event JSON
                                 (open in Perfetto / chrome://tracing)
  GET  /api/fg/{fg}/doctor/    → flight-recorder dump + bottleneck attribution
                                 (telemetry/doctor.py; ``?md=1`` renders
                                 markdown instead of JSON)
  GET  /api/fg/{fg}/profile/   → live profile plane: compile counters/storms
                                 + per-program roofline (telemetry/profile.py;
                                 ``?costs=1`` materializes lazy cost analyses)

plus the multi-tenant serving session plane (docs/serving.md, merged from
``futuresdr_tpu/serve/api.py``):

  GET/POST/DELETE /api/serve/...  → serving apps, session admit/evict/
                                    readmit/leave, per-session metrics views,
                                    graceful drain (POST .../drain/)

plus the orchestrator lifecycle endpoints on EVERY control port (rolling
restarts, docs/serving.md "Lifecycle"):

  GET /healthz   → liveness (the event loop answers)
  GET /readyz    → readiness: serving apps compiled + not draining, no
                   serving-program compile storm on the profile plane (503 + Retry-After
                   otherwise)

plus the fleet observability plane (telemetry/fleet.py + serve/router.py,
docs/observability.md "The fleet plane"):

  GET  /api/host/                        → this host's lock-free pressure
                                           summary (every control port)
  GET  /api/fleet/                       → aggregated readyz + per-host
                                           table + cross-host verdicts
  GET  /api/fleet/metrics                → merged Prometheus exposition
                                           (host= label, stable ordering)
  POST /api/fleet/serve/{app}/session/   → pressure-routed admission
                                           (least-pressure ready host,
                                           failover honoring Retry-After)

Pmt values are serialized with the same externally-tagged JSON as the reference's serde.
CORS is permissive (including on error responses raised as ``web.HTTPException``);
graceful shutdown on ``stop()``.
"""

from __future__ import annotations

import asyncio
import threading
from typing import Optional

from ..config import config
from ..log import logger
from ..types import Pmt

__all__ = ["ControlPort"]

log = logger("ctrl_port")


class ControlPort:
    def __init__(self, runtime_handle, bind: Optional[str] = None, extra_routes=None):
        """``extra_routes``: list of ("GET"|"POST", path, async handler) tuples merged
        into the app — the `examples/custom-routes` extension point."""
        self.handle = runtime_handle
        bind = bind or config().ctrlport_bind
        host, _, port = bind.partition(":")
        self.host = host or "127.0.0.1"
        self.port = int(port or 1337)
        self.extra_routes = list(extra_routes or [])
        self._fleet_router = None          # lazy AdmissionRouter (fleet on)
        self._thread: Optional[threading.Thread] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._started = threading.Event()
        self._runner = None

    # -- server lifecycle (own thread, like the reference's tokio thread) ------
    def start(self) -> None:
        if self._thread is not None:
            return

        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            loop.run_until_complete(self._serve())
            self._started.set()
            loop.run_forever()
            loop.run_until_complete(self._cleanup())
            loop.close()

        self._thread = threading.Thread(target=run, name="fsdr-ctrlport", daemon=True)
        self._thread.start()
        self._started.wait(timeout=10)

    def stop(self) -> None:
        if self._loop is not None and self._loop.is_running():
            self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._thread = None

    async def _cleanup(self):
        if self._runner is not None:
            await self._runner.cleanup()

    # -- routes ----------------------------------------------------------------
    async def _serve(self):
        from aiohttp import web

        app = web.Application()

        @web.middleware
        async def cors(request, handler):
            try:
                resp = await handler(request)
            except web.HTTPException as e:
                # a handler (extra_routes especially) may RAISE its error
                # response; aiohttp serves the exception object directly, so
                # it must carry the CORS header too or browser clients see an
                # opaque failure instead of the 4xx/5xx body
                e.headers["Access-Control-Allow-Origin"] = "*"
                raise
            resp.headers["Access-Control-Allow-Origin"] = "*"
            return resp

        app.middlewares.append(cors)
        app.router.add_get("/metrics", self._prometheus)
        app.router.add_get("/api/fg/", self._list_fgs)
        app.router.add_get("/api/fg/{fg}/", self._describe_fg)
        app.router.add_get("/api/fg/{fg}/metrics/", self._metrics)
        app.router.add_get("/api/fg/{fg}/trace/", self._trace)
        app.router.add_get("/api/fg/{fg}/doctor/", self._doctor)
        app.router.add_get("/api/fg/{fg}/profile/", self._profile)
        app.router.add_get("/api/fg/{fg}/lineage/", self._lineage)
        app.router.add_get("/api/events/", self._events)
        app.router.add_get("/api/fg/{fg}/block/{blk}/", self._describe_block)
        app.router.add_get("/api/fg/{fg}/block/{blk}/call/{handler}/", self._call)
        app.router.add_post("/api/fg/{fg}/block/{blk}/call/{handler}/", self._call)
        # multi-tenant serving session plane (futuresdr_tpu/serve/api.py,
        # docs/serving.md): the registry is process-global like /metrics and
        # the doctor, so every control port serves it
        try:
            from ..serve import api as serve_api
            for method, path, handler in serve_api.routes():
                app.router.add_route(method, path, handler)
        except Exception as e:             # noqa: BLE001 — optional plane
            log.warning("serve session plane unavailable: %r", e)

            # the lifecycle endpoints must exist on EVERY control port even
            # with the serve plane unimportable — an orchestrator's probes
            # are not optional. The fallback retries the real readyz lazily
            # (the import failure may be transient); while the plane stays
            # unavailable readiness is UNKNOWN, so it answers 503 with a
            # clamped Retry-After default — a fleet poller or load balancer
            # must back off, not hammer (nor route to) a half-imported pod
            async def _healthz_fallback(request):
                return web.json_response({"ok": True})

            async def _readyz_fallback(request):
                try:
                    from ..serve import api as _serve_api
                    return await _serve_api.readyz(request)
                except Exception as err:   # noqa: BLE001 — still broken
                    return web.json_response(
                        {"ready": False, "apps": {},
                         "error": f"serve plane unavailable: {err!r}"},
                        status=503, headers={"Retry-After": "1"})

            app.router.add_get("/healthz", _healthz_fallback)
            app.router.add_get("/readyz", _readyz_fallback)
        # fleet observability plane (telemetry/fleet.py, docs/
        # observability.md "The fleet plane"): the per-host export is on
        # every control port; the aggregated views answer from the process
        # FleetView, which only polls when `fleet_peers` is configured
        app.router.add_get("/api/host/", self._host_summary)
        app.router.add_get("/api/fleet/", self._fleet)
        app.router.add_get("/api/fleet/metrics", self._fleet_metrics)
        app.router.add_post("/api/fleet/serve/{app}/session/",
                            self._fleet_admit)
        try:
            from ..telemetry import fleet as _fleet
            _fleet.ensure_started()
        except Exception as e:             # noqa: BLE001 — optional plane
            log.warning("fleet plane unavailable: %r", e)
        for method, path, handler in self.extra_routes:
            app.router.add_route(method, path, handler)
        import os
        fp = config().frontend_path
        if not fp:
            builtin = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "gui")
            fp = builtin if os.path.isdir(builtin) else None
        if fp:
            index = os.path.join(fp, "index.html")

            async def serve_index(request):
                return web.FileResponse(index)

            app.router.add_get("/", serve_index)
            app.router.add_static("/static/", fp)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        log.info("control port listening on %s:%d", self.host, self.port)

    async def _list_fgs(self, request):
        from aiohttp import web
        return web.json_response(self.handle.flowgraph_ids())

    def _fg(self, request):
        return self.handle.get_flowgraph(int(request.match_info["fg"]))

    async def _describe_fg(self, request):
        from aiohttp import web
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"}, status=404)
        desc = await fg.describe()
        return web.json_response(desc.to_json())

    async def _metrics(self, request):
        from aiohttp import web
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"}, status=404)
        return web.json_response(await fg.metrics())

    async def _prometheus(self, request):
        """Prometheus text exposition: global registry + per-block families of
        every live flowgraph (``WrappedKernel.metrics()`` stays the single
        source; ``telemetry/prom.py`` only renders the dicts)."""
        from aiohttp import web

        from ..telemetry import profile, prom
        try:
            # refresh fsdr_mfu/fsdr_hbm_util from the dispatch window since
            # the previous scrape (telemetry/profile.py; min_interval keeps
            # a scrape storm from shrinking the window into noise) — only
            # materialized program costs publish, so a scrape never compiles
            profile.plane().update_live_gauges()
        except Exception as e:                   # noqa: BLE001 — scrape must
            log.warning("profile gauge refresh failed: %r", e)   # not fail
        fg_metrics = {}
        for fg_id in self.handle.flowgraph_ids():
            fg = self.handle.get_flowgraph(fg_id)
            if fg is None:
                continue
            try:
                fg_metrics[fg_id] = await fg.metrics()
            except Exception as e:               # noqa: BLE001 — scrape must
                log.warning("metrics scrape of fg %d failed: %r", fg_id, e)
        if request.query.get("openmetrics"):
            # OpenMetrics exposition: exemplars on histogram buckets (the
            # lineage trace ids behind fsdr_e2e_latency_seconds) + # EOF;
            # per-block families keep the shared v0.0.4-compatible text
            from ..telemetry import prom as _p
            body = _p.registry().render_openmetrics()
            if fg_metrics:
                body = body[:-len("# EOF\n")] \
                    + prom.render_block_metrics(fg_metrics) + "# EOF\n"
            return web.Response(body=body.encode(),
                                headers={"Content-Type":
                                         prom.CONTENT_TYPE_OPENMETRICS})
        return web.Response(body=prom.render_all(fg_metrics).encode(),
                            headers={"Content-Type": prom.CONTENT_TYPE})

    async def _trace(self, request):
        """Drain the span ring as Chrome trace-event JSON (Perfetto-loadable).
        404 for unknown flowgraphs to match the /api/fg/ family; the ring is
        process-global, so any live fg id drains the same recorder. The drain
        is a DESTRUCTIVE read — a poller that must not steal events from
        another trace consumer (a second client's ``GET …/trace/``, the
        benchmark's own drain) passes ``?keep=1`` for a non-draining snapshot
        instead."""
        from aiohttp import web

        from ..telemetry import spans
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"}, status=404)
        rec = spans.recorder()
        events = rec.snapshot() if request.query.get("keep") else rec.drain()
        return web.json_response(rec.chrome_trace(events))

    async def _doctor(self, request):
        """Explicit flight-recorder trigger + bottleneck attribution (the
        operator's "why is this flowgraph stuck" endpoint). Uses the
        NON-destructive span snapshot so a concurrent trace consumer
        (``benchmark/drivers``, ``GET …/trace/``) keeps its events; 404s for
        unknown flowgraphs to match the ``/api/fg/`` family (the doctor is
        process-global, like the trace ring)."""
        import json as _json

        from aiohttp import web

        from ..telemetry import doctor as doc
        from ..telemetry import spans
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"},
                                     status=404)
        d = doc.doctor()
        record = d.flight_record("endpoint")
        if request.query.get("md"):
            return web.Response(text=doc.render_markdown(record),
                                content_type="text/markdown")
        body = {"report": d.report(events=spans.recorder().snapshot()),
                "flight_record": record}
        # default=str: span args / extra_metrics may carry numpy scalars
        return web.json_response(
            body, dumps=lambda o: _json.dumps(o, default=str))

    async def _profile(self, request):
        """The live profile plane (telemetry/profile.py): per-program
        compile counters/reasons, active compiles, recompile-storm
        classification, and the live roofline table (registered
        flops/bytes per unit, windowed + run-average MFU/HBM-util,
        hbm/compute-bound classification). ``?costs=1`` materializes
        lazily-registered cost analyses first — that may compile once per
        program signature, so it runs off the event loop; the default view
        never compiles. 404s for unknown flowgraphs to match the
        ``/api/fg/`` family (the plane is process-global, like the trace
        ring and the doctor)."""
        import asyncio
        import json as _json

        from aiohttp import web

        from ..telemetry import profile
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"},
                                     status=404)
        ensure = bool(request.query.get("costs"))
        if ensure:
            snap = await asyncio.get_running_loop().run_in_executor(
                None, lambda: profile.plane().snapshot(ensure_costs=True))
        else:
            # default min_interval: a polling client must not shrink the
            # gauge window into per-dispatch noise (same guard as /metrics)
            profile.plane().update_live_gauges()
            snap = profile.plane().snapshot()
        return web.json_response(
            snap, dumps=lambda o: _json.dumps(o, default=str))

    async def _lineage(self, request):
        """Sampled frame-lineage view (telemetry/lineage.py): the tail
        attribution report plus the most recent completed records
        (``?n=<count>``, default 32, stamps with lane/thread detail). The
        read is non-destructive — the tracer's done ring keeps feeding the
        doctor and the Perfetto flow export. 404s for unknown flowgraphs to
        match the ``/api/fg/`` family (the tracer is process-global, like
        the trace ring)."""
        from aiohttp import web

        from ..telemetry import lineage
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"},
                                     status=404)
        try:
            n = max(0, int(request.query.get("n", 32)))
        except ValueError:
            return web.json_response({"error": "bad n"}, status=400)
        tr = lineage.tracer()
        return web.json_response({
            "stride": tr.stride,
            "dropped": tr.dropped,
            "tail": lineage.tail_report(),
            "records": tr.records_dicts(n or None),
        })

    async def _events(self, request):
        """Journal cursor read (telemetry/journal.py): ``?since=<seq>`` (0 =
        from the oldest retained), ``?cat=<category>`` filter, ``?limit=``
        page size. The response carries ``next`` (pass back as the next
        ``since``), ``seq`` (the newest seq emitted so far) and ``gap``
        (true when the ring already evicted events past the cursor — the
        JSONL spool, ``journal_dir``, has the full history). Process-global
        like /metrics, so it is NOT fg-scoped."""
        from aiohttp import web

        from ..telemetry import journal
        q = request.query
        try:
            since = int(q.get("since", 0))
            limit = int(q["limit"]) if "limit" in q else None
        except ValueError:
            return web.json_response({"error": "bad since/limit"}, status=400)
        cat = q.get("cat") or None
        return web.json_response(
            journal.journal().events(since=since, cat=cat, limit=limit))

    async def _host_summary(self, request):
        """The per-host fleet export (telemetry/fleet.py): one cheap,
        lock-free summary — host id, uptime, readyz verdict, per-app shed
        rung + credit pressure + session counts, windowed MFU/HBM-util,
        compile-storm flag, doctor verdict, e2e p50/p99, journal cursor
        head. Built on the health()/retry_after_s() discipline, so a
        wedged step() holding an engine lock never stalls a fleet poll."""
        import json as _json

        from aiohttp import web

        from ..telemetry import fleet
        return web.json_response(
            fleet.host_summary(),
            dumps=lambda o: _json.dumps(o, default=str))

    def _fleet_view(self):
        from ..telemetry import fleet
        return fleet.ensure_started()

    async def _fleet(self, request):
        """Aggregated fleet view: readyz rollup + per-host table + cross-
        host verdicts. 404 while the fleet plane is disabled (no
        ``fleet_peers`` configured) — same shape as an unknown-fg error."""
        import json as _json

        from aiohttp import web
        view = self._fleet_view()
        if view is None:
            return web.json_response(
                {"error": "fleet plane disabled (set fleet_peers)"},
                status=404)
        return web.json_response(
            view.snapshot(), dumps=lambda o: _json.dumps(o, default=str))

    async def _fleet_metrics(self, request):
        """Merged Prometheus exposition across the fleet (``host=`` label,
        stable ordering). The per-peer scrapes are blocking HTTP, so the
        merge runs off the event loop."""
        import asyncio

        from aiohttp import web

        from ..telemetry import prom
        view = self._fleet_view()
        if view is None:
            return web.json_response(
                {"error": "fleet plane disabled (set fleet_peers)"},
                status=404)
        body = await asyncio.get_running_loop().run_in_executor(
            None, view.merged_metrics)
        return web.Response(body=body.encode(),
                            headers={"Content-Type": prom.CONTENT_TYPE})

    async def _fleet_admit(self, request):
        """Pressure-routed admission (serve/router.py): pick the least-
        pressure ready host, POST the admit there, fail over on 503
        honoring Retry-After; every decision journals with the scores
        considered. The remote admit is blocking HTTP — executor."""
        import asyncio

        from aiohttp import web

        from ..serve.router import AdmissionRouter, NoReadyHost
        view = self._fleet_view()
        if view is None:
            return web.json_response(
                {"error": "fleet plane disabled (set fleet_peers)"},
                status=404)
        if self._fleet_router is None:
            self._fleet_router = AdmissionRouter(view)
        name = request.match_info["app"]
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:              # noqa: BLE001 — bad JSON → 400
                return web.json_response(
                    {"error": "bad json body", "app": name}, status=400)
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self._fleet_router.admit(
                    name, tenant=str(body.get("tenant", "default")),
                    sid=body.get("sid"), body=body))
        except NoReadyHost as e:
            return web.json_response(
                {"error": str(e), "app": name}, status=503,
                headers={"Retry-After": str(e.retry_after)})
        return web.json_response(out, status=201)

    async def _describe_block(self, request):
        from aiohttp import web
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"}, status=404)
        desc = await fg.describe()
        blk = int(request.match_info["blk"])
        for b in desc.blocks:
            if b.id == blk:
                return web.json_response(b.to_json())
        return web.json_response({"error": "block not found"}, status=404)

    async def _call(self, request):
        from aiohttp import web
        fg = self._fg(request)
        if fg is None:
            return web.json_response({"error": "flowgraph not found"}, status=404)
        blk = int(request.match_info["blk"])
        handler = request.match_info["handler"]
        try:
            handler = int(handler)
        except ValueError:
            pass
        if request.method == "POST":
            try:
                pmt = Pmt.from_json(await request.json())
            except Exception as e:
                return web.json_response({"error": f"bad pmt: {e}"}, status=400)
        else:
            pmt = Pmt.null()
        result = await fg.call(blk, handler, pmt)
        return web.json_response(result.to_json())
