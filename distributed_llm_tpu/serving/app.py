"""Flask chat API — reference contract preserved verbatim.

Reference parity: src/app.py.  Endpoints and JSON fields are identical so
the reference's React frontend points at this server unchanged:

  POST /chat       {message, strategy, session_id} ->
                   {reply, device, reasoning, method, confidence,
                    cache_hit, tokens}
  GET  /history    ?session_id=...   -> [messages]
  DELETE /history  ?session_id=...   -> {"cleared": session_id}

Behavioral details kept: UI strategy name "token-counting" maps to "token"
(app.py:37-38); strategy switches go through QueryRouter.change_strategy so
cache + perf state survive (app.py:46-53); per-session history capped at the
last 10 messages (app.py:23); the just-appended user message is rolled back
if routing raises (app.py:96-97).  Fixed (documented drift): session state
lives behind a lock — the reference's bare globals are a known hazard under
a threaded server (SURVEY.md §5.2).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, List, Optional

from ..config import ClusterConfig
from ..utils.http_compat import (Flask, enable_cors, jsonify, request,
                                 sse_done_event, sse_event, static_response,
                                 streaming_response)
from .router import Router

logger = logging.getLogger(__name__)

HISTORY_LIMIT = 10

# Input-hardening cap on one /chat message, in characters.  Far above any
# real prompt (every tier's context truncates earlier — overflow_policy /
# prepare_prompt), low enough that a hostile body can't make the session
# store or the tokenizer chew megabytes before the edge says no.
MAX_MESSAGE_CHARS = 65536

# Same defaults the reference app passes (src/app.py:9-14).
BASE_CONFIG: Dict[str, Any] = {
    "cache_enabled": True,
    "enable_response_cache": True,
    "enable_failover": True,
    "weights": {"token": 0.25, "semantic": 0.45, "heuristic": 0.30},
}


def create_app(router: Optional[Router] = None,
               cluster: Optional[ClusterConfig] = None) -> Flask:
    app = Flask("distributed_llm_tpu")
    enable_cors(app)

    state_lock = threading.Lock()
    if router is None:
        router = Router(strategy="hybrid", config=dict(BASE_CONFIG),
                        cluster=cluster)
    state = {
        "router": router,
        "strategy": router.query_router.strategy,
        "histories": {},      # session_id -> List[message]
    }
    app.extensions["dllm_state"] = state

    @app.route("/chat", methods=["POST"])
    def chat():
        err, turn, requested, session_id, tenant_id, history, snapshot = \
            _begin_chat_turn()
        if err is not None:
            return err

        try:
            response_data, tokens, device = state["router"].route_query(
                snapshot, session_id=session_id, tenant_id=tenant_id)

            if isinstance(response_data, dict):
                reply = response_data.get("response", "")
                reasoning = response_data.get(
                    "routing_reasoning", f"Method: {requested}")
                method = response_data.get("routing_method", requested)
                confidence = response_data.get("routing_confidence", 0.0)
                cache_hit = response_data.get("cache_hit", False)
            else:
                reply = str(response_data)
                reasoning, method = "Direct response", requested
                confidence, cache_hit = 0.0, False

            _commit_assistant_turn(history, session_id, reply)

            return jsonify({
                "reply": reply,
                "device": device,
                "reasoning": reasoning,
                "method": method,
                "confidence": confidence,
                "cache_hit": cache_hit,
                "tokens": tokens,
            })

        except Exception as exc:
            logger.exception("Error during routing")
            _rollback_user_turn(history, turn)
            return jsonify({
                "reply": "System Error: The router encountered an issue.",
                "device": "error",
                "reasoning": str(exc),
                "method": requested,
                "confidence": 0.0,
                "cache_hit": False,
                "tokens": 0,
            }), 500

    def _bad_request(msg: str):
        """One 400 shape for every input-hardening rejection (reference
        error dict, like the original missing-message branch)."""
        return ((jsonify({"error": msg}), 400),
                None, None, None, None, None, None)

    def _begin_chat_turn():
        """Shared /chat + /chat/stream front half: parse AND VALIDATE the
        request, hot-swap the strategy, append the user turn.  Returns
        (error_response | None, user_input, requested, session_id,
        tenant_id, history, snapshot).

        Input hardening: bad JSON / non-object bodies, non-string or
        oversized messages, and non-string strategy/session_id/tenant_id
        are all 400 with the reference error shape — before this, only a
        missing message was caught and a non-string one crashed
        downstream in the tokenizer.  ``tenant_id`` (ISSUE 17, additive
        field) is capped at 64 chars and must be printable — it becomes
        a metric label and a quota key; absent means the shared
        ``default`` tenant, so tenant-less clients are unchanged."""
        if getattr(state["router"], "draining", False):
            # Graceful drain: the edge stops admitting FIRST.  503 + the
            # sanctioned retry hint; in-flight requests keep finishing.
            return ((jsonify({
                "error": "Request failed: server is draining "
                         "(graceful shutdown in progress)",
                "retry_after_s": state["router"].drain_retry_after_s(),
            }), 503), None, None, None, None, None, None)
        data = request.get_json(silent=True)
        if data is None:
            return _bad_request("Request failed: body must be valid JSON")
        if not isinstance(data, dict):
            return _bad_request("Request failed: body must be a JSON "
                                "object")
        user_input = data.get("message", "")
        requested = data.get("strategy", "hybrid")
        session_id = data.get("session_id", "default")
        tenant_id = data.get("tenant_id", "default")
        if not isinstance(user_input, str):
            return _bad_request("Request failed: 'message' must be a "
                                "string")
        if len(user_input) > MAX_MESSAGE_CHARS:
            return _bad_request(f"Request failed: 'message' exceeds "
                                f"{MAX_MESSAGE_CHARS} characters")
        if not isinstance(requested, str) or not isinstance(session_id,
                                                            str):
            return _bad_request("Request failed: 'strategy' and "
                                "'session_id' must be strings")
        if not isinstance(tenant_id, str) or not tenant_id:
            return _bad_request("Request failed: 'tenant_id' must be a "
                                "non-empty string")
        if len(tenant_id) > 64:
            return _bad_request("Request failed: 'tenant_id' exceeds "
                                "64 characters")
        if any(ord(c) < 32 or ord(c) == 127 for c in tenant_id):
            return _bad_request("Request failed: 'tenant_id' must not "
                                "contain control characters")
        if requested == "token-counting":   # UI dropdown name
            requested = "token"
        if not user_input.strip():
            return _bad_request("No message provided")
        with state_lock:
            if requested != state["strategy"]:
                logger.info("Switching strategy: %s -> %s",
                            state["strategy"], requested)
                try:
                    state["router"].query_router.change_strategy(requested)
                    state["strategy"] = requested
                except Exception as exc:
                    return ((jsonify({"error":
                                      f"Failed to switch strategy: {exc}"}),
                             500), None, None, None, None, None, None)
            history = state["histories"].setdefault(session_id, [])
            turn = {"role": "user", "content": user_input}
            history.append(turn)
            snapshot = list(history)
        return (None, turn, requested, session_id, tenant_id, history,
                snapshot)

    def _rollback_user_turn(history, turn):
        """Remove THIS request's user turn by identity — popping the tail
        would delete a different request's turn when two land on the same
        session concurrently (streams hold the window open for seconds)."""
        with state_lock:
            for i in range(len(history) - 1, -1, -1):
                if history[i] is turn:
                    del history[i]
                    break

    def _commit_assistant_turn(history, session_id, reply):
        """Append the assistant turn and trim IN PLACE: replacing the list
        object would orphan the reference every other in-flight request on
        this session holds — and NO re-bind, which would resurrect a
        session cleared (or replaced) while this request was in flight."""
        with state_lock:
            history.append({"role": "assistant", "content": reply})
            if len(history) > HISTORY_LIMIT:
                del history[:len(history) - HISTORY_LIMIT]

    @app.route("/chat/stream", methods=["POST"])
    def chat_stream():
        """SSE chat: one ``meta`` event with the routing decision, then
        ``delta`` events as tokens decode, then ``done``.  The reference
        API is non-streaming (stream:false, src/devices/nano_api.py:67);
        this is the TTFT-native extension of /chat, built on
        Router.route_query_stream — the SAME decision stage, setup-time
        failover, fault model, and perf feedback as the sync path.  The
        response cache does not participate (a stream is consumed as it
        is produced)."""
        err, turn, requested, session_id, tenant_id, history, snapshot = \
            _begin_chat_turn()
        if err is not None:
            return err

        try:
            routed = state["router"].route_query_stream(
                snapshot, session_id=session_id, tenant_id=tenant_id)
        except Exception as exc:
            logger.exception("stream routing failed")
            _rollback_user_turn(history, turn)
            return jsonify({"error": f"Routing failed: {exc}"}), 500

        def events():
            pieces: List[str] = []
            committed = False
            try:
                yield sse_event({"meta": True, **routed.meta})
                for delta in routed:
                    pieces.append(delta)
                    yield sse_event({"delta": delta})
                _commit_assistant_turn(history, session_id, "".join(pieces))
                committed = True
                yield sse_done_event(routed.result)
            except Exception as exc:
                logger.exception("stream failed mid-flight")
                yield sse_event({"error": str(exc)})
            finally:
                # Covers errors AND client disconnects (GeneratorExit
                # skips except-Exception): an uncommitted turn must not
                # leave the session history with this request's dangling
                # user message.
                if not committed:
                    _rollback_user_turn(history, turn)

        return streaming_response(events())

    # -- frontend (reference: fyp-chat-frontend, served here dependency-
    # free — same /chat contract, so the original React app also works
    # pointed at this server) --------------------------------------------
    frontend_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "frontend")
    ui_files = {                  # fixed allowlist: no path traversal
        "/ui": ("index.html", "text/html; charset=utf-8"),
        "/ui/app.js": ("app.js", "application/javascript; charset=utf-8"),
        "/ui/style.css": ("style.css", "text/css; charset=utf-8"),
    }

    def _serve_ui(route: str):
        fname, ctype = ui_files[route]
        path = os.path.join(frontend_dir, fname)
        if not os.path.exists(path):
            return jsonify({"error": "frontend not bundled"}), 404
        with open(path, "rb") as f:
            return static_response(f.read(), ctype)

    def _make_ui_view(route: str):
        def view():
            return _serve_ui(route)
        # Distinct names: real Flask derives its endpoint from __name__.
        view.__name__ = "ui_" + ui_files[route][0].replace(".", "_")
        return view

    for route in ui_files:
        app.route(route, methods=["GET"])(_make_ui_view(route))

    @app.route("/health", methods=["GET"])
    def health():
        """Process-level liveness for load balancers and drain
        orchestration: ``status`` is ``draining`` (503) once a graceful
        drain started, else ``ok``.  Per-tier snapshots ride along —
        manager.health() is lock-free, so this never blocks behind a
        mid-compile lifecycle lock."""
        router_ = state["router"]
        draining = bool(getattr(router_, "draining", False))
        tiers = {}
        for name, tier in router_.tiers.items():
            try:
                tiers[name] = tier.server_manager.health()
            except Exception as exc:
                tiers[name] = {"ok": False, "detail": str(exc)[:200]}
        payload = {"status": "draining" if draining else "ok",
                   "draining": draining,
                   "tiers": tiers}
        if draining:
            payload["retry_after_s"] = router_.drain_retry_after_s()
            return jsonify(payload), 503
        return jsonify(payload)

    @app.route("/metrics", methods=["GET"])
    def metrics():
        """Prometheus text exposition of the serving metric registry
        (obs/metrics.py): TTFT/TBT/queue-wait histograms, admission
        rejects, breaker transitions + state, watchdog wedges, cache
        hits, degraded count.  Scrape-friendly twin of GET /stats."""
        router_ = state["router"]
        export = getattr(router_, "export_tick_totals", None)
        if callable(export):
            export()          # dllm_tick_phase_ms_total as of this scrape
        body = router_.obs.metrics.render().encode("utf-8")
        return static_response(
            body, "text/plain; version=0.0.4; charset=utf-8")

    @app.route("/debug/trace", methods=["GET"])
    def debug_trace():
        """Chrome-trace/Perfetto JSON of every live engine's tick-phase
        profiler ring (obs/profiler.py): ticks as slices, phases as
        nested child slices with self-times, compile/host-sync instants
        stitched in.  Load it in chrome://tracing or ui.perfetto.dev —
        the "why did that tick cost 40 ms" surface.  Empty traceEvents
        when no profiler is live (DLLM_PROFILE=0, sequential tiers).
        ``?since=<s>&until=<s>`` (wall-clock seconds, either may be
        left out) cut the ring to a window after the fact; ``metadata``
        gives the origin of ``ts`` on ``time.time()`` and
        ``time.perf_counter()``."""
        window = {}
        for key in ("since", "until"):
            raw = request.args.get(key)
            if raw is None:
                continue
            try:
                window[key] = float(raw)
            except ValueError:
                return jsonify({"error": f"Request failed: '{key}' must "
                                         f"be a number of seconds"}), 400
        router_ = state["router"]
        fn = getattr(router_, "profiler_trace", None)
        body = fn(**window) if callable(fn) else {"traceEvents": []}
        return jsonify(body)

    @app.route("/debug/programs", methods=["GET"])
    def debug_programs():
        """Every live engine's step programs, operation by operation:
        ``{"tiers": {<tier>: [{"stage": "decode" | "chunk_prefill",
        "program": "jit_decode_tick" | "jit_chunk_prefill",
        "window_tokens", "chunk_tokens", "attention_form", "built_s":
        {"lower", "compile", "read"}, "pool_sized_moves": {<opcode>:
        <count>}, "ops": {<HLO instruction>:
        {"scope": <innermost jax.named_scope or null>, "mixed":
        <bool>}}}]}}`` — the join between a device trace's operation
        names and the model code's scopes (obs/program_scopes.py), and
        beside it the count of the pool-sized arrays the program copies
        on its way in, round a loop or out (``{}``: it leaves the pool
        where it rests);
        ``ops`` stands in the compiled text's own order (a reader parts
        two programs that hold the same names by it).
        ``?stage=decode|chunk_prefill`` and ``?window_tokens=<n>[,<n>]``
        select programs; ``?ops=0`` lists them and builds nothing.  The
        first request about a program compiles it again, under a cache
        key that holds the metadata, and the engine keeps the answer; a
        process that never asks pays nothing.  Not the profiler's:
        DLLM_PROFILE=0 leaves it on."""
        select = {"ops": request.args.get("ops", "1") != "0"}
        stage = request.args.get("stage")
        if stage is not None:
            if stage not in ("decode", "chunk_prefill"):
                return jsonify({"error": "Request failed: 'stage' is "
                                         "decode or chunk_prefill"}), 400
            select["stage"] = stage
        raw = request.args.get("window_tokens")
        if raw is not None:
            try:
                select["window_tokens"] = [int(w) for w in raw.split(",")]
            except ValueError:
                return jsonify({"error": "Request failed: 'window_tokens' "
                                         "must be whole numbers"}), 400
        fn = getattr(state["router"], "step_programs", None)
        return jsonify(fn(**select) if callable(fn) else {"tiers": {}})

    @app.route("/stats", methods=["GET"])
    def stats():
        """Observability snapshot (SURVEY.md §5.5): routing-cache health,
        per-tier engine state + phase timings, device memory.  With
        ``?debug=1``: the flight recorder's ring — full span trees +
        serving-state snapshots of the last N failed/degraded/slow
        requests (obs/recorder.py) — for post-mortems."""
        from ..utils.telemetry import device_memory_snapshot
        with state_lock:
            router_ = state["router"]
            strategy = state["strategy"]
            sessions = len(state["histories"])
        tiers = {}
        for name, tier in router_.tiers.items():
            mgr = tier.server_manager
            entry = dict(mgr.health())
            # Peek without lazy-starting; remote tiers' managers
            # (serving/remote.py) have no local engine at all.
            from ..utils.telemetry import engine_stats
            subs = getattr(mgr, "live_engines", None)
            if callable(subs):
                # Replicated tier (ISSUE 12): per-replica engine stats
                # nested under their replica keys, plus the manager's
                # summed kv picture at tier level.
                entry["replica_engines"] = {
                    key: engine_stats(engine) for key, engine in subs()}
                kv_fn = getattr(mgr, "kv_stats", None)
                agg = kv_fn() if callable(kv_fn) else None
                if agg:
                    entry["kv"] = agg
            else:
                entry.update(engine_stats(getattr(mgr, "_engine", None)))
            # Per-tenant quota state (ISSUE 17): active counts, token-
            # bucket levels, admit/reject totals — quota-ON tiers only.
            tq = getattr(tier, "tenants", None)
            if tq is not None:
                try:
                    entry["tenants"] = tq.snapshot()
                except Exception:
                    pass
            tiers[name] = entry
        try:
            cache_stats = router_.query_router.get_cache_stats()
        except Exception:
            cache_stats = None
        import jax as _jax
        payload = {
            "strategy": strategy,
            "sessions": sessions,
            "cache": cache_stats,
            "tiers": tiers,
            "devices": device_memory_snapshot(),
            "measured_tables": {"backend": _jax.default_backend()},
            "prefix_affinity_overrides": getattr(
                router_, "prefix_affinity_overrides", 0),
            # Fault-tolerance observability (serving/breaker.py): per-tier
            # circuit state + how many requests the degraded path served.
            "breaker": (router_.breaker.snapshot()
                        if getattr(router_, "breaker", None) is not None
                        else None),
            "degraded_served": getattr(router_, "degraded_served", 0),
            # Degradation cause in ONE call: per-tier draining flags next
            # to the breaker states, and the SLO monitor's windowed
            # goodput + incident state (obs/slo.py) — an operator seeing
            # goodput collapse reads WHY (circuit open? draining? queue?)
            # without a second scrape.
            "draining": {
                name: bool(getattr(t.server_manager, "draining", False))
                for name, t in router_.tiers.items()},
            "slo": (router_.slo.snapshot()
                    if getattr(router_, "slo", None) is not None
                    else None),
            # Elastic capacity (ISSUE 18, serving/autoscaler.py): live
            # membership, streak/cooldown state, and the bounded
            # decision ledger per armed tier — why capacity moved, next
            # to the goodput/breaker evidence that moved it.  None when
            # no tier arms the autoscaler (or DLLM_AUTOSCALE=0).
            "autoscaler": (router_.autoscaler_snapshot()
                           if callable(getattr(router_,
                                               "autoscaler_snapshot",
                                               None))
                           else None),
            # Per-(tier, strategy, session) attributed cost (ISSUE 11):
            # decode device time + KV block-ticks from the bounded
            # ledger _finish_request feeds — who pays for the ticks,
            # in one call.
            "cost": (router_.cost_snapshot()
                     if callable(getattr(router_, "cost_snapshot", None))
                     else None),
        }
        if request.args.get("timeline") == "1":
            # The system-state timeline ring (obs/sampler.py): per-tier
            # queue/slot/KV/breaker/tick trajectory at the sampler's
            # cadence — samples once on demand for an idle router.
            fn = getattr(router_, "timeline_snapshot", None)
            payload["timeline"] = fn() if callable(fn) else []
            sampler = getattr(router_, "sampler", None)
            if sampler is not None:
                payload["timeline_meta"] = {
                    "period_s": sampler.period_s,
                    "capacity": sampler.capacity,
                    "samples_total": sampler.samples_total,
                    "sample_cost_ms": (round(sampler.sample_cost_ms, 4)
                                       if sampler.sample_cost_ms is not None
                                       else None),
                    "running": sampler.running,
                }
        if request.args.get("debug") == "1":
            obs = getattr(router_, "obs", None)
            if obs is not None:
                payload["flight_recorder"] = obs.recorder.snapshot()
                payload["flight_recorded_total"] = \
                    obs.recorder.recorded_total
        return jsonify(payload)

    @app.route("/history", methods=["GET"])
    def get_history():
        session_id = request.args.get("session_id", "default")
        with state_lock:
            return jsonify(state["histories"].get(session_id, []))

    @app.route("/history", methods=["DELETE"])
    def clear_history():
        session_id = request.args.get("session_id", "default")
        with state_lock:
            state["histories"].pop(session_id, None)
        return jsonify({"cleared": session_id})

    return app


def install_drain_handler(router: Router, exit_after: bool = True) -> bool:
    """SIGTERM → graceful drain (shared by the API server and the CLI):
    stop admitting (the edge 503s, /health flips to ``draining``), let
    in-flight requests finish under each tier's ``drain_timeout_s``, stop
    the engines, then exit.  Returns False when no handler could be
    installed (non-main thread — e.g. an app built inside a test
    worker)."""
    import signal

    def _on_sigterm(signum, frame):
        logger.warning("SIGTERM: draining before exit")
        try:
            router.drain()
        finally:
            if exit_after:
                raise SystemExit(0)

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
        return True
    except ValueError:            # not the main thread: caller's problem
        return False


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    import jax

    from ..config import describe_cluster
    from ..utils.compile_cache import enable_persistent_compile_cache
    from .router import default_cluster
    cache_dir = enable_persistent_compile_cache()
    devices = jax.devices()
    cluster = default_cluster()
    # First line of a server's life: what it runs on and what it serves,
    # so a server that came up on the host CPU (tiny test tiers) says so.
    logger.info(
        "serving on platform=%s device_kind=%s count=%d; cluster: %s; "
        "compile cache: %s", devices[0].platform, devices[0].device_kind,
        len(devices), describe_cluster(cluster), cache_dir)
    router = Router(strategy="hybrid", config=dict(BASE_CONFIG),
                    cluster=cluster)
    app = create_app(router=router)
    install_drain_handler(router)
    print("🚀 API running on http://0.0.0.0:8000")
    app.run(host="0.0.0.0", port=8000, threaded=True)


if __name__ == "__main__":
    main()
