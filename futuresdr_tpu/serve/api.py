"""REST session plane for the serving front-end.

Extends the control port (``runtime/ctrl_port.py``) with the multi-tenant
session API of docs/serving.md — the routes are merged into every control
port automatically (plus available as ``routes()`` for a bespoke server):

  GET    /api/serve/                         → registered serving apps
  GET    /api/serve/{app}/                   → engine view (slots, buckets,
                                               per-tenant credit/latency)
  POST   /api/serve/{app}/session/           → admit  {"tenant": "...",
                                               "sid": optional}
  GET    /api/serve/{app}/session/{sid}/     → per-session metrics/doctor view
  POST   /api/serve/{app}/session/{sid}/evict/   → evict carry to host
  POST   /api/serve/{app}/session/{sid}/readmit/ → restore it bit-identically
  POST   /api/serve/{app}/session/{sid}/ctrl/    → lane-addressed retune
                                               {"stage": ..., "params": {...}}
  DELETE /api/serve/{app}/session/{sid}/     → leave
  POST   /api/serve/{app}/drain/             → graceful drain (refuse
                                               admissions, finish in-flight,
                                               persist all lanes)

plus the orchestrator lifecycle endpoints the control port mounts at the
server root (docs/serving.md "Lifecycle"):

  GET /healthz  → liveness (the process answers)
  GET /readyz   → readiness: every registered serving app compiled, not
                  draining, and the profile plane reports no serving-program storm
                  (503 + Retry-After otherwise)

Error responses are structured JSON (``{"error": ..., "app": ...}``), and
every 503 (ServeFull / draining / overload shed) carries a ``Retry-After``
header derived from the engine's measured step rate.

Engines register under an app name via :func:`register_app` (usually at
construction by the app's serving loop); the registry is process-global,
matching the control port's own process-global planes (/metrics, doctor).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from ..log import logger
from .slots import ServeFull

__all__ = ["register_app", "unregister_app", "get_app", "apps", "routes",
           "readiness", "healthz", "readyz", "readyz_retry_after"]

log = logger("serve.api")

# app name -> ServeEngine; the module deliberately depends only on the
# jax-free bookkeeping side (slots) so a host-only control port can merge
# these routes without importing the compute plane
_apps: Dict[str, "object"] = {}
_lock = threading.Lock()


def register_app(engine, name: Optional[str] = None) -> str:
    """Register a :class:`~futuresdr_tpu.serve.engine.ServeEngine` under an
    app name (default: its own ``app``). With config
    ``serve_drain_on_sigterm`` set, the first registration also installs
    the SIGTERM graceful-drain hook (rolling-restart lifecycle)."""
    name = str(name or engine.app)
    with _lock:
        _apps[name] = engine
    try:
        from ..config import config
        if config().get("serve_drain_on_sigterm", False):
            from .engine import install_sigterm_drain
            install_sigterm_drain()
    except Exception as e:                 # noqa: BLE001 — lifecycle sugar
        log.warning("sigterm drain hook unavailable: %r", e)
    return name


def unregister_app(name: str) -> None:
    with _lock:
        _apps.pop(str(name), None)


def get_app(name: str):
    with _lock:
        return _apps.get(str(name))


def apps() -> Dict[str, "object"]:
    with _lock:
        return dict(_apps)


# -- aiohttp handlers ---------------------------------------------------------

async def _call(fn, *args, **kw):
    """Run a blocking engine call off the event loop: surgery methods
    (evict/readmit/retune) contend on the engine's STEP lock, which a
    stepper holds across an entire dispatch — including a newly-resident
    capacity's jit compile (seconds on a real backend). Calling them inline
    would freeze every other control-port route (/metrics scrapes, doctor,
    flowgraph APIs) for that long. (Read-only views only take the narrow
    state lock, but they ride the executor too — uniformity is cheaper
    than auditing each handler's lock discipline.)"""
    import asyncio
    import functools
    return await asyncio.get_running_loop().run_in_executor(
        None, functools.partial(fn, *args, **kw))


def _json_error(app: Optional[str], message: str, status: int,
                retry_after: Optional[int] = None):
    """Structured JSON error body (``{"error": ..., "app": ...}``) with the
    ``Retry-After`` header on backpressure statuses — a client or load
    balancer reads WHEN to come back instead of hammering a 503."""
    from aiohttp import web
    headers = {"Retry-After": str(int(retry_after))} \
        if retry_after is not None else None
    return web.json_response({"error": message, "app": app},
                             status=status, headers=headers)


def _serve_full(eng, name: str, e: BaseException):
    """503 for ServeFull/ServeDraining/ServeOverload, Retry-After derived
    from the engine's measured step rate."""
    try:
        after = int(eng.retry_after_s())
    except Exception:                      # noqa: BLE001 — header is advisory
        after = 1
    return _json_error(name, str(e), 503, retry_after=after)


def _engine_or_404(request):
    from aiohttp import web
    name = request.match_info["app"]
    eng = get_app(name)
    if eng is None:
        raise web.HTTPNotFound(
            text='{"error": "serving app not found", "app": "%s"}' % name,
            content_type="application/json")
    return eng


async def _list_apps(request):
    from aiohttp import web
    return web.json_response(
        {name: {"sessions": len(eng.table.sessions),
                "active": eng.table.active,
                "capacity": eng.capacity,
                "draining": bool(getattr(eng, "draining", False))}
         for name, eng in sorted(apps().items())})


async def _describe_app(request):
    from aiohttp import web
    return web.json_response(await _call(_engine_or_404(request).describe))


async def _create_session(request):
    from aiohttp import web
    eng = _engine_or_404(request)
    name = request.match_info["app"]
    body = {}
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:                  # noqa: BLE001 — bad JSON → 400
            return _json_error(name, "bad json body", 400)
    tenant = str(body.get("tenant", "default"))
    try:
        s = await _call(eng.admit, tenant=tenant, sid=body.get("sid"))
    except ServeFull as e:
        return _serve_full(eng, name, e)
    except ValueError as e:
        return _json_error(name, str(e), 409)
    # the answer names the format this engine's submit() takes: one per
    # engine, so a client knows at admission what to send
    return web.json_response({**s.view(), **eng.frame_format()}, status=201)


async def _session_view(request):
    from aiohttp import web
    eng = _engine_or_404(request)
    try:
        return web.json_response(
            await _call(eng.session_view, request.match_info["sid"]))
    except KeyError:
        return _json_error(request.match_info["app"], "session not found",
                           404)


async def _session_evict(request):
    from aiohttp import web
    eng = _engine_or_404(request)
    name = request.match_info["app"]
    try:
        s = await _call(eng.evict, request.match_info["sid"])
    except KeyError:
        return _json_error(name, "session not found", 404)
    except ValueError as e:
        return _json_error(name, str(e), 409)
    return web.json_response(s.view())


async def _session_readmit(request):
    from aiohttp import web
    eng = _engine_or_404(request)
    name = request.match_info["app"]
    try:
        s = await _call(eng.readmit, request.match_info["sid"])
    except KeyError:
        return _json_error(name, "session not found", 404)
    except ServeFull as e:
        return _serve_full(eng, name, e)
    except ValueError as e:
        return _json_error(name, str(e), 409)
    return web.json_response(s.view())


async def _session_ctrl(request):
    """``POST /api/serve/{app}/session/{sid}/ctrl/``: lane-addressed
    retune — apply an ``update_stage`` hook to ONE session's carry page at
    the lane's next quiescent boundary, siblings untouched. Body
    ``{"stage": <name|index>, "params": {...}}``; a bad stage address or a
    stage without an update hook is a 409 on this app's contract (the
    session exists — the REQUEST is wrong)."""
    from aiohttp import web
    eng = _engine_or_404(request)
    name = request.match_info["app"]
    try:
        body = await request.json()
        stage = body["stage"]
        params = body.get("params") or {}
        if not isinstance(params, dict):
            raise TypeError("params must be an object")
    except (ValueError, KeyError, TypeError):
        return _json_error(name, "bad json body: expected "
                           '{"stage": ..., "params": {...}}', 400)
    try:
        s = await _call(eng.retune, request.match_info["sid"], stage,
                        **params)
    except KeyError:
        return _json_error(name, "session not found", 404)
    except (ValueError, TypeError) as e:
        return _json_error(name, str(e), 409)
    return web.json_response(s.view())


async def _session_delete(request):
    from aiohttp import web
    eng = _engine_or_404(request)
    try:
        await _call(eng.close, request.match_info["sid"])
    except KeyError:
        return _json_error(request.match_info["app"], "session not found",
                           404)
    return web.json_response({"ok": True})


async def _drain_app(request):
    """``POST /api/serve/{app}/drain/``: graceful drain — refuse new
    admissions (503 + Retry-After), finish in-flight megabatch groups,
    persist every live lane, report drained. Runs off the event loop (the
    pump steps the engine); body ``{"pump": false}`` only MARKS draining
    for apps with their own pump thread, ``{"timeout": s}`` bounds the
    pump."""
    from aiohttp import web
    eng = _engine_or_404(request)
    name = request.match_info["app"]
    body = {}
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:                  # noqa: BLE001
            body = {}
    try:
        report = await _call(eng.drain,
                             pump=bool(body.get("pump", True)),
                             timeout=float(body.get("timeout", 30.0)))
    except Exception as e:                 # noqa: BLE001 — drain must report
        return _json_error(name, f"drain failed: {e!r}", 500)
    return web.json_response(report)


# -- orchestrator lifecycle (healthz/readyz) ----------------------------------

def readiness() -> Tuple[bool, dict]:
    """Process readiness for ``GET /readyz``: every registered serving app
    ready (current bucket compiled, not draining) AND no live SERVING-
    program compile storm on the profile plane. Detail names the unready app/reason so an
    operator reads WHY a pod is out of rotation."""
    detail: Dict[str, dict] = {}
    ready = True
    for name, eng in sorted(apps().items()):
        try:
            h = eng.health()
        except Exception as e:             # noqa: BLE001 — an engine that
            h = {"ready": False, "error": repr(e)}     # cannot answer is
        detail[name] = h                               # not ready
        ready = ready and bool(h.get("ready"))
    storms = None
    try:
        from ..telemetry import profile
        # SERVING-program storms only ("serve:<app>" labels): the plane is
        # process-global and flowgraph instance names collide across runs
        # by design, so an unrelated kernel's recompile churn must not pull
        # this pod out of rotation — a churning slot-bucket ladder must
        storms = [s for s in profile.plane().storm_report()
                  if str(s.get("program", "")).startswith("serve:")] or None
    except Exception:                      # noqa: BLE001 — profile plane
        pass                               # absence must not fail readiness
    if storms:
        ready = False
    return ready, {"apps": detail, "compile_storms": storms}


async def healthz(request):
    """Liveness: the process (and its control-port event loop) answers."""
    from aiohttp import web
    return web.json_response({"ok": True})


def readyz_retry_after() -> int:
    """The Retry-After default of an unready 503: the largest registered
    engine's measured ``retry_after_s()`` (lock-free), clamped to [1, 30]
    like the engines' own estimate — a fleet poller or load balancer backs
    off by how long this pod actually needs, not a hardcoded second."""
    after = 1
    for _name, eng in apps().items():
        try:
            after = max(after, int(eng.retry_after_s()))
        except Exception:                  # noqa: BLE001 — advisory header
            pass
    return int(min(30, max(1, after)))


async def readyz(request):
    """Readiness for rolling restarts: 200 only when every serving app is
    compiled + not draining with no serving-program compile storm;
    503 (+ clamped Retry-After) otherwise so an orchestrator holds
    traffic."""
    from aiohttp import web
    ready, detail = readiness()
    if ready:
        return web.json_response({"ready": True, **detail})
    return web.json_response(
        {"ready": False, **detail}, status=503,
        headers={"Retry-After": str(readyz_retry_after())})


def routes() -> List[Tuple[str, str, object]]:
    """The session-plane route table, in control-port ``extra_routes``
    form (method, path, handler)."""
    return [
        ("GET", "/api/serve/", _list_apps),
        ("GET", "/api/serve/{app}/", _describe_app),
        ("POST", "/api/serve/{app}/session/", _create_session),
        ("GET", "/api/serve/{app}/session/{sid}/", _session_view),
        ("POST", "/api/serve/{app}/session/{sid}/evict/", _session_evict),
        ("POST", "/api/serve/{app}/session/{sid}/readmit/", _session_readmit),
        ("POST", "/api/serve/{app}/session/{sid}/ctrl/", _session_ctrl),
        ("DELETE", "/api/serve/{app}/session/{sid}/", _session_delete),
        ("POST", "/api/serve/{app}/drain/", _drain_app),
        ("GET", "/healthz", healthz),
        ("GET", "/readyz", readyz),
    ]
