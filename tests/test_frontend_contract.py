"""Frontend ↔ server contract.

No JS runtime ships in this image, so ``frontend/app.js`` cannot be
EXECUTED against the server the way the reference React app runs in a
browser (App.tsx:100-109).  Instead this suite makes drift mechanical to
catch: it SCRAPES app.js for every endpoint it calls, every request-body
key it sends, and every response field it reads, then drives the real
WSGI app and asserts the server actually serves that surface.  Renaming
or dropping a field on either side fails here.
"""

import json
import os
import re

import pytest

from distributed_llm_tpu.config import ClusterConfig, TierConfig
from distributed_llm_tpu.serving.app import create_app
from distributed_llm_tpu.serving.router import Router

APP_JS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "frontend", "app.js")
INDEX_HTML = os.path.join(os.path.dirname(APP_JS), "index.html")


@pytest.fixture(scope="module")
def js() -> str:
    with open(APP_JS) as f:
        return f.read()


@pytest.fixture(scope="module")
def client():
    cluster = ClusterConfig(
        nano=TierConfig(name="nano", model_preset="nano_test",
                        max_new_tokens=8, prefill_buckets=(16, 32, 64),
                        kv_block_size=16),
        orin=TierConfig(name="orin", model_preset="orin_test",
                        max_new_tokens=8, prefill_buckets=(16, 32, 64),
                        kv_block_size=16),
    )
    router = Router(strategy="heuristic", cluster=cluster)
    app = create_app(router=router)
    return app.test_client()


def scraped_endpoints(js):
    """Every path app.js fetches: `API_BASE + "/chat"` etc., query
    strings stripped."""
    paths = set()
    for m in re.finditer(r'API_BASE \+ "([^"]+)"', js):
        paths.add(m.group(1).split("?")[0])
    return paths


def test_every_scraped_endpoint_exists(js, client):
    paths = scraped_endpoints(js)
    # The scrape must keep finding the known surface — if the frontend
    # switches to a URL-building helper this test must be updated, not
    # silently pass on an empty set.
    assert {"/chat", "/chat/stream", "/history"} <= paths, paths
    for path in paths:
        # 404 = unrouted; anything else (200/400/405) proves the route
        # is registered on the server.
        assert client.get(path).status_code != 404, path
        assert client.post(path, json={}).status_code != 404, path


def test_chat_request_and_response_fields_match(js, client):
    # Request keys the frontend sends (chatBody).
    body_src = re.search(r"function chatBody.*?\{(.*?)\}\);", js,
                         re.S).group(1)
    sent_keys = set(re.findall(r"(\w+):", body_src))
    assert sent_keys == {"message", "strategy", "session_id"}

    rv = client.post("/chat", json={"message": "hello there",
                                    "strategy": "heuristic",
                                    "session_id": "fc1"})
    assert rv.status_code == 200
    data = rv.get_json()

    # Response fields the frontend reads: data.<f> in the sync path plus
    # everything metaPanel renders via addBotMessage(data) (d.<f>).
    read_fields = set(re.findall(r"\bdata\.(\w+)", js))
    read_fields |= set(re.findall(r"\bd\.(\w+)", js))
    read_fields -= {"error"}          # error-shape only (asserted below)
    assert read_fields == {"reply", "device", "method", "confidence",
                           "cache_hit", "reasoning", "tokens"}, read_fields
    missing = read_fields - set(data)
    assert not missing, f"/chat response lacks fields app.js reads: {missing}"

    # The !res.ok branch reads data.reply || data.error.
    bad = client.post("/chat", json={"message": "   "})
    assert bad.status_code == 400
    assert {"reply", "error"} & set(bad.get_json() or {}), bad.get_json()


def test_stream_events_cover_frontend_handlers(js, client):
    """sendStreaming dispatches on ev.meta / ev.delta / ev.done /
    ev.error and reads meta.device/method/confidence/cache_hit/reasoning
    and ev.tokens — the SSE stream must emit exactly that shape."""
    ev_fields = set(re.findall(r"\bev\.(\w+)", js))
    assert {"meta", "delta", "done", "error", "tokens"} <= ev_fields
    meta_fields = set(re.findall(r"meta && meta\.(\w+)", js))
    assert meta_fields == {"device", "method", "confidence", "cache_hit",
                           "reasoning"}

    rv = client.post("/chat/stream", json={"message": "stream hi",
                                           "session_id": "fc2"})
    assert rv.status_code == 200
    assert "text/event-stream" in rv.content_type
    events = [json.loads(line[len("data: "):])
              for line in rv.text.strip().split("\n\n")
              if line.startswith("data: ")]
    metas = [e for e in events if e.get("meta")]
    dones = [e for e in events if e.get("done")]
    assert len(metas) == 1 and len(dones) == 1, events
    assert meta_fields <= set(metas[0]), metas[0]
    assert "tokens" in dones[0], dones[0]
    assert any("delta" in e for e in events)


def test_history_roundtrip_shape(js, client):
    """restore() expects GET /history to return a JSON array of
    {role, content}; the clear button issues DELETE /history."""
    assert re.search(r'm\.role === "user"', js)
    assert re.search(r"m\.content", js)
    client.post("/chat", json={"message": "remember me",
                               "session_id": "fc3"})
    rv = client.get("/history?session_id=fc3")
    hist = rv.get_json()
    assert isinstance(hist, list) and hist
    for m in hist:
        assert {"role", "content"} <= set(m)
    assert client.delete("/history?session_id=fc3").status_code == 200
    assert client.get("/history?session_id=fc3").get_json() == []


def test_strategy_options_accepted_by_server(client):
    """Every <option> value in index.html must be a strategy the server
    accepts (including the reference's 'token-counting' UI alias,
    src/app.py:37-38)."""
    with open(INDEX_HTML) as f:
        html = f.read()
    options = re.findall(r'<option value="([^"]+)"', html)
    assert options, "no strategy options found in index.html"
    for opt in options:
        rv = client.post("/chat", json={"message": "strategy check",
                                        "strategy": opt,
                                        "session_id": f"fc-{opt}"})
        assert rv.status_code == 200, (opt, rv.get_json())


def test_ui_served_routes(client):
    """The SPA itself is served at /ui (app.js, index.html, styles)."""
    for route in ("/ui", "/ui/app.js"):
        assert client.get(route).status_code == 200, route
