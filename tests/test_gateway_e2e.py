"""Gateway-routed platform E2E (VERDICT r1 item 5's done-criterion):
requests flow client → gateway (annotation-discovered routes, forward-auth
via gatekeeper) → real backends (model server, jupyter web app) against the
fake cluster — the ambassador + basic-auth + web-app stack over real
sockets (kubeflow/common/ambassador.libsonnet:7-226,
components/gatekeeper/auth/AuthServer.go:32-210,
jupyter-web-app routes.py:33-168)."""

import hashlib
import json
import threading
import urllib.error
import urllib.request

import pytest

from kubeflow_tpu.auth.gatekeeper import AuthService, make_server as \
    make_auth_server
from kubeflow_tpu.gateway import Gateway, RouteTable
from kubeflow_tpu.manifests.core import generate
from kubeflow_tpu.serving.engine import EngineConfig
from kubeflow_tpu.serving.server import ModelServer
from kubeflow_tpu.webapps.jupyter import JupyterApp, make_server as \
    make_jupyter_server


def http(method, url, payload=None, headers=None):
    req = urllib.request.Request(
        url, method=method,
        data=json.dumps(payload).encode() if payload is not None else None,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req) as r:
        return r.status, json.loads(r.read() or b"{}"), r.headers


@pytest.fixture()
def platform(api):
    """Fake cluster + live backends + gateway with resolved routes."""
    servers = []

    # Model server (the tpu-serving Deployment's process).
    model = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=32),
        port=0, batch_timeout_ms=2,
    )
    model.start()
    servers.append(model.stop)

    # Jupyter web app against the fake apiserver.
    japp = make_jupyter_server(JupyterApp(api, "jax-notebook:latest"), 0)
    threading.Thread(target=japp.serve_forever, daemon=True).start()
    servers.append(japp.shutdown)
    jport = japp.server_address[1]

    # Apply the rendered serving + webapp manifests so routes come from
    # REAL annotations (the same objects kfctl deploys), plus the Notebook
    # CRD the web app's CRs require.
    from kubeflow_tpu.apis.notebooks import notebook_crd

    api.apply(notebook_crd())
    for obj in generate("tpu-serving", {"name": "lm", "model_path": "",
                                        "namespace": "kubeflow"}):
        api.apply(obj)
    for obj in generate("jupyter-web-app", {"namespace": "kubeflow"}):
        api.apply(obj)

    table = RouteTable()
    n = table.refresh(api)
    assert n >= 2

    # In-cluster service addresses → local fixture ports.
    backends = {
        "lm.kubeflow:8500": f"127.0.0.1:{model.port}",
        "jupyter-web-app.kubeflow:80": f"127.0.0.1:{jport}",
    }
    gw = Gateway(table, port=0, admin_port=0,
                 resolve=lambda addr: backends.get(addr, addr))
    gw.start()
    servers.append(gw.stop)
    base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"
    yield api, gw, base
    for stop in servers:
        stop()


def test_predict_routed_through_gateway(platform):
    _api, _gw, base = platform
    code, out, _ = http(
        "POST", f"{base}/models/lm/v1/models/lm-test-tiny:predict",
        {"instances": [{"tokens": [1, 2, 3]}]},
    )
    assert code == 200
    assert len(out["predictions"]) == 1
    assert isinstance(out["predictions"][0]["next_token"], int)


def test_notebook_crud_routed_through_gateway(platform):
    api, _gw, base = platform
    # The jupyter-web-app route prefix comes from its Service annotation.
    code, out, _ = http(
        "POST", f"{base}/jupyter/api/namespaces/kubeflow/notebooks",
        {"name": "nb1", "tpuChips": 4, "workspace": {"size": "10Gi"}},
    )
    assert code == 201, out
    # CR + PVC landed in the fake cluster.
    nb = api.get("kubeflow-tpu.org/v1", "Notebook", "nb1", "kubeflow")
    assert nb["spec"]["tpu"]["chips"] == 4
    assert api.get("v1", "PersistentVolumeClaim", "nb1-workspace", "kubeflow")

    code, listing, _ = http(
        "GET", f"{base}/jupyter/api/namespaces/kubeflow/notebooks")
    assert [n["name"] for n in listing["notebooks"]] == ["nb1"]


def test_unrouted_path_404s(platform):
    _api, _gw, base = platform
    with pytest.raises(urllib.error.HTTPError) as e:
        http("GET", f"{base}/no/such/route")
    assert e.value.code == 404


def test_gateway_forward_auth_with_gatekeeper(api):
    """401 without a session; login at the gatekeeper mints a cookie the
    gateway accepts (basic-auth ingress semantics)."""
    auth = AuthService("admin",
                       hashlib.sha256(b"hunter2").hexdigest())
    auth_httpd = make_auth_server(auth, 0)
    threading.Thread(target=auth_httpd.serve_forever, daemon=True).start()
    auth_port = auth_httpd.server_address[1]

    # One echo backend behind the gateway.
    from kubeflow_tpu.gateway import Route

    table = RouteTable()
    table.set_routes([Route("auth", "/login", f"127.0.0.1:{auth_port}",
                            rewrite="/login"),
                      Route("gk", "/gk/", f"127.0.0.1:{auth_port}",
                            rewrite="/")])
    gw = Gateway(table, port=0, admin_port=0,
                 auth_url=f"http://127.0.0.1:{auth_port}/auth")
    gw.start()
    base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            http("GET", f"{base}/gk/healthz")
        assert e.value.code == 401

        # Login directly at the gatekeeper → cookie (raw client: urllib
        # follows the 302 and drops the Set-Cookie of the redirect itself).
        from http.client import HTTPConnection

        conn = HTTPConnection("127.0.0.1", auth_port)
        conn.request("POST", "/login", b"username=admin&password=hunter2",
                     {"Content-Type": "application/x-www-form-urlencoded"})
        resp = conn.getresponse()
        assert resp.status == 302
        cookie = resp.getheader("Set-Cookie")
        conn.close()
        assert cookie
        cookie = cookie.split(";")[0]

        code, out, _ = http("GET", f"{base}/gk/healthz",
                            headers={"Cookie": cookie})
        assert code == 200 and out["status"] == "ok"

        # Wrong password never mints a session.
        req = urllib.request.Request(
            f"http://127.0.0.1:{auth_port}/login",
            data=b"username=admin&password=wrong", method="POST",
            headers={"Content-Type": "application/x-www-form-urlencoded"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 401
    finally:
        gw.stop()
        auth_httpd.shutdown()


def test_admission_webhook_mutates_labeled_pods():
    """gcp-admission-webhook semantics (main.go:131-158): a pod labeled with
    a cred secret gains the secret volume + mount + env; TPU containers gain
    platform env; unlabeled CPU pods pass through unpatched."""
    import base64

    from kubeflow_tpu.auth.webhook import (
        CRED_LABEL,
        mutate_pod,
        review_response,
    )

    pod = {
        "kind": "Pod",
        "metadata": {"labels": {CRED_LABEL: "user-gcp-sa"}},
        "spec": {"containers": [
            {"name": "main",
             "resources": {"limits": {"google.com/tpu": 4}}},
        ]},
    }
    patches = mutate_pod(pod)
    paths = [p["path"] for p in patches]
    assert "/spec/volumes" in paths
    assert "/spec/containers/0/volumeMounts" in paths
    env_values = [p["value"] for p in patches if "env" in p["path"]]
    flat = [e for v in env_values for e in (v if isinstance(v, list) else [v])]
    names = {e["name"] for e in flat}
    assert {"GOOGLE_APPLICATION_CREDENTIALS", "JAX_PLATFORMS",
            "TPU_MIN_LOG_LEVEL"} <= names
    # A pod that asked for chips runs on them or fails: no CPU fallback.
    assert {e["name"]: e["value"] for e in flat}["JAX_PLATFORMS"] == "tpu"

    assert mutate_pod({"kind": "Pod", "metadata": {},
                       "spec": {"containers": [{"name": "c"}]}}) == []

    review = review_response({
        "apiVersion": "admission.k8s.io/v1",
        "request": {"uid": "u1", "object": pod},
    })
    assert review["response"]["allowed"]
    decoded = json.loads(base64.b64decode(review["response"]["patch"]))
    assert decoded == patches


def test_gateway_tls_termination(tmp_path):
    """HTTPS at the gateway (the iap-ingress/cert-manager role): requests
    over TLS reach routed backends; the manifest mounts the cert Secret."""
    import ssl
    import subprocess

    from kubeflow_tpu.gateway import Route

    cert = tmp_path / "tls.crt"
    key = tmp_path / "tls.key"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )
    table = RouteTable()
    gw = Gateway(table, port=0, admin_port=0,
                 certfile=str(cert), keyfile=str(key))
    gw.start()
    base = f"https://127.0.0.1:{gw._proxy.server_address[1]}"
    try:
        ctx = ssl.create_default_context()
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE
        with urllib.request.urlopen(f"{base}/healthz", context=ctx) as r:
            assert r.status == 200
        # Plain HTTP against the TLS port fails.
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{gw._proxy.server_address[1]}/healthz",
                timeout=5)
    finally:
        gw.stop()

    # The gateway prototype wires the cert Secret through to the flags.
    objs = generate("gateway", {"tls_secret": "gateway-tls"})
    dep = [o for o in objs if o["kind"] == "Deployment"][0]
    container = dep["spec"]["template"]["spec"]["containers"][0]
    assert "--tls-cert=/etc/tls/tls.crt" in container["args"]
    assert dep["spec"]["template"]["spec"]["volumes"][0]["secret"][
        "secretName"] == "gateway-tls"


def _ws_accept(key: str) -> str:
    import base64

    guid = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"
    return base64.b64encode(
        hashlib.sha1((key + guid).encode()).digest()
    ).decode()


class _WsEchoServer:
    """Minimal RFC6455 echo backend: real handshake (Sec-WebSocket-Accept),
    then echoes every masked text frame back unmasked."""

    def __init__(self):
        import socket

        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self.handshake_headers = []
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._session, args=(conn,),
                             daemon=True).start()

    def _session(self, conn):
        try:
            data = b""
            while b"\r\n\r\n" not in data:
                data += conn.recv(4096)
            head = data.split(b"\r\n\r\n", 1)[0].decode()
            headers = {}
            for line in head.split("\r\n")[1:]:
                k, _, v = line.partition(":")
                headers[k.strip().lower()] = v.strip()
            self.handshake_headers.append(headers)
            if headers.get("upgrade", "").lower() != "websocket":
                conn.sendall(b"HTTP/1.1 400 Bad Request\r\n"
                             b"Content-Length: 0\r\n\r\n")
                conn.close()
                return
            accept = _ws_accept(headers["sec-websocket-key"])
            conn.sendall(
                ("HTTP/1.1 101 Switching Protocols\r\n"
                 "Upgrade: websocket\r\nConnection: Upgrade\r\n"
                 f"Sec-WebSocket-Accept: {accept}\r\n\r\n").encode()
            )
            while True:
                hdr = conn.recv(2)
                if len(hdr) < 2:
                    return
                ln = hdr[1] & 0x7F
                mask = conn.recv(4)
                payload = bytearray(conn.recv(ln))
                for i in range(ln):
                    payload[i] ^= mask[i % 4]
                if hdr[0] & 0x0F == 0x8:  # close frame
                    conn.close()
                    return
                conn.sendall(bytes([0x81, ln]) + bytes(payload))
        except OSError:
            pass

    def close(self):
        self.sock.close()


def test_websocket_echo_through_gateway(api):
    """An Upgrade handshake through the gateway becomes a transparent TCP
    tunnel: the backend's 101 reaches the client and masked frames echo
    back — the jupyter.libsonnet:97-106 `use_websocket` capability."""
    import base64
    import os
    import socket

    from kubeflow_tpu.gateway import Route

    echo = _WsEchoServer()
    table = RouteTable()
    table.set_routes([Route(name="nb", prefix="/nb/",
                            service=f"127.0.0.1:{echo.port}")])
    gw = Gateway(table, port=0, admin_port=0)
    gw.start()
    try:
        port = gw._proxy.server_address[1]
        client = socket.create_connection(("127.0.0.1", port), timeout=10)
        key = base64.b64encode(os.urandom(16)).decode()
        client.sendall(
            (f"GET /nb/kernel HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
             "Upgrade: websocket\r\nConnection: Upgrade\r\n"
             f"Sec-WebSocket-Key: {key}\r\n"
             "Sec-WebSocket-Version: 13\r\n\r\n").encode()
        )
        resp = b""
        while b"\r\n\r\n" not in resp:
            resp += client.recv(4096)
        assert b"101" in resp.split(b"\r\n", 1)[0]
        assert _ws_accept(key).encode() in resp  # real handshake, not 200
        # Send one masked text frame; expect the echoed unmasked frame.
        msg = b"ping-through-gateway"
        mask = os.urandom(4)
        masked = bytes(b ^ mask[i % 4] for i, b in enumerate(msg))
        client.sendall(bytes([0x81, 0x80 | len(msg)]) + mask + masked)
        frame = b""
        while len(frame) < 2 + len(msg):
            frame += client.recv(4096)
        assert frame[0] == 0x81
        assert frame[2:2 + len(msg)] == msg
        # The backend saw the forwarded prefix header; tunnel was counted.
        assert echo.handshake_headers[0]["x-forwarded-prefix"] == "/nb/"
        assert gw.tunnels_total == 1
        client.close()
    finally:
        gw.stop()
        echo.close()


def test_streaming_chunked_response_not_buffered(api):
    """A slow chunked upstream must stream through the gateway: the first
    chunk arrives while the backend is still holding the connection open
    (token-stream / SSE readiness; VERDICT r2 missing #2)."""
    import socket
    import time
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from kubeflow_tpu.gateway import Route

    release = threading.Event()

    class SlowChunks(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            for i, wait in ((0, False), (1, True)):
                if wait:
                    release.wait(timeout=10)
                data = f"data: tok{i}\n\n".encode()
                self.wfile.write(f"{len(data):x}\r\n".encode() + data
                                 + b"\r\n")
                self.wfile.flush()
            self.wfile.write(b"0\r\n\r\n")

    backend = ThreadingHTTPServer(("127.0.0.1", 0), SlowChunks)
    threading.Thread(target=backend.serve_forever, daemon=True).start()
    table = RouteTable()
    table.set_routes([Route(name="s", prefix="/stream/",
                            service=f"127.0.0.1:{backend.server_address[1]}")])
    gw = Gateway(table, port=0, admin_port=0)
    gw.start()
    try:
        import http.client

        conn = http.client.HTTPConnection(
            "127.0.0.1", gw._proxy.server_address[1], timeout=10)
        conn.request("GET", "/stream/events")
        resp = conn.getresponse()
        assert resp.status == 200
        # First chunk is readable while the backend still blocks on the
        # release event — i.e. the gateway did NOT buffer the whole body.
        first = resp.read1(65536)
        assert b"tok0" in first
        release.set()
        rest = b""
        while True:
            data = resp.read1(65536)
            if not data:
                break
            rest += data
        assert b"tok1" in rest
        conn.close()
    finally:
        release.set()
        gw.stop()
        backend.shutdown()


class _IdentityBackend:
    """HTTP backend answering with its own name (+ records requests)."""

    def __init__(self, name, port=0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.name = name
        self.requests = []
        outer = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _reply(self):
                outer.requests.append({
                    "path": self.path,
                    "shadow": self.headers.get("X-Shadow", ""),
                })
                body = json.dumps({"variant": outer.name}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            do_GET = do_POST = _reply

        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()  # release the listen socket too


def test_weighted_traffic_split_through_gateway(api):
    """VERDICT r2 next #4 done-criterion: 100 requests split ~90/10
    between two model-server variants, from the rendered serving-route
    prototype's annotation (seldon abtest surface)."""
    import random

    from kubeflow_tpu.manifests.core import generate

    primary, canary = _IdentityBackend("primary"), _IdentityBackend("canary")
    # The model's own tpu-serving Service carries a plain route at the
    # SAME prefix — the canary serving-route must win the tie, or the
    # split is silently dead.
    for obj in generate("tpu-serving", {"name": "bert", "model_path": ""}):
        api.apply(obj)
    svc = generate("serving-route", {
        "name": "bert", "canary_service": "bert-v2.kubeflow:8500",
        "canary_weight": 10,
    })[0]
    api.apply(svc)
    table = RouteTable()
    assert table.refresh(api) == 2
    assert table.match("/models/bert/x").backends  # split route wins

    backends = {
        "bert.kubeflow:8500": f"127.0.0.1:{primary.port}",
        "bert-v2.kubeflow:8500": f"127.0.0.1:{canary.port}",
    }
    gw = Gateway(table, port=0, admin_port=0,
                 resolve=lambda a: backends.get(a, a),
                 rng=random.Random(7))
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"
        hits = {"primary": 0, "canary": 0}
        for _ in range(100):
            _, out, _ = http("GET", f"{base}/models/bert/v1/models")
            hits[out["variant"]] += 1
        assert hits["primary"] + hits["canary"] == 100
        assert 80 <= hits["primary"] <= 97, hits
        assert 3 <= hits["canary"] <= 20, hits
    finally:
        gw.stop()
        primary.close()
        canary.close()


class _FailingBackend:
    """HTTP backend that always answers 500 (a broken model variant)."""

    def __init__(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _reply(self):
                body = b'{"error": "broken variant"}'
                self.send_response(500)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            do_GET = do_POST = _reply

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_epsilon_greedy_bandit_routes_around_failures(api):
    """The seldon multi-armed-bandit surface: an epsilon-greedy route
    learns from response statuses — a variant answering 500s converges
    to only the exploration share of traffic, no manual weight change."""
    import random

    from kubeflow_tpu.manifests.core import generate

    good, bad = _IdentityBackend("good"), _FailingBackend()
    svc = generate("serving-route", {
        "name": "bert", "canary_service": "bert-v2.kubeflow:8500",
        "strategy": "epsilon-greedy", "epsilon": 0.2,
    })[0]
    api.apply(svc)
    table = RouteTable()
    table.refresh(api)
    route = table.match("/models/bert/x")
    assert route.strategy == "epsilon-greedy"

    backends = {
        "bert.kubeflow:8500": f"127.0.0.1:{good.port}",
        "bert-v2.kubeflow:8500": f"127.0.0.1:{bad.port}",
    }
    gw = Gateway(table, port=0, admin_port=0,
                 resolve=lambda a: backends.get(a, a),
                 rng=random.Random(11))
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"
        statuses = []
        for _ in range(100):
            try:
                code, _out, _ = http("GET", f"{base}/models/bert/v1/models")
            except urllib.error.HTTPError as e:
                code = e.code
            statuses.append(code)
        # Exploration is 20% split over 2 arms → ~10% of traffic still
        # probes the broken variant; exploitation goes to the healthy one.
        failures = sum(1 for s in statuses if s == 500)
        assert failures <= 25, failures
        assert statuses.count(200) >= 75
        stats = gw.bandit.snapshot("bert-route")
        assert stats["bert.kubeflow:8500"]["mean"] == 1.0
        assert stats["bert-v2.kubeflow:8500"]["mean"] == 0.0
        assert (stats["bert.kubeflow:8500"]["trials"]
                > stats["bert-v2.kubeflow:8500"]["trials"])
    finally:
        gw.stop()
        good.close()
        bad.close()


def test_bandit_feedback_endpoint_steers_routing(api):
    """Explicit rewards (the seldon /send-feedback analogue) through the
    admin API flip the bandit's preference between two healthy variants,
    and /routes exposes the per-variant stats."""
    import random

    from kubeflow_tpu.gateway import Route

    a, b = _IdentityBackend("a"), _IdentityBackend("b")
    table = RouteTable()
    table.set_routes([Route(
        name="m", prefix="/m/",
        service=f"127.0.0.1:{a.port}",
        backends=((f"127.0.0.1:{a.port}", 1), (f"127.0.0.1:{b.port}", 1)),
        strategy="epsilon-greedy", epsilon=0.0,  # pure exploitation
    )])
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        admin_port = s.getsockname()[1]
    gw = Gateway(table, port=0, admin_port=admin_port,
                 rng=random.Random(3))
    gw.start()
    try:
        admin = f"http://127.0.0.1:{admin_port}"
        # Grade variant b higher than every status-derived reward can be
        # beaten by: a gets 0.2, b gets 1.0.
        code, out, _ = http("POST", f"{admin}/routes/m/feedback",
                            {"service": f"127.0.0.1:{a.port}",
                             "reward": 0.2})
        assert code == 200 and out["ok"]
        http("POST", f"{admin}/routes/m/feedback",
             {"service": f"127.0.0.1:{b.port}", "reward": 1.0})

        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"
        hits = {"a": 0, "b": 0}
        for _ in range(20):
            _, out, _ = http("GET", f"{base}/m/x")
            hits[out["variant"]] += 1
        # b keeps winning: its implicit 200-rewards sustain mean 1.0
        # while a stays anchored by the 0.2 grade.
        assert hits["b"] == 20, hits

        code, routes, _ = http("GET", f"{admin}/routes")
        m = next(r for r in routes if r["name"] == "m")
        assert m["bandit"][f"127.0.0.1:{b.port}"]["trials"] >= 20
        # The admin view annotates copies, not the live Route objects —
        # a second snapshot must show identical structure, and the route
        # the proxy matches must not have grown a 'bandit' attribute.
        _, routes2, _ = http("GET", f"{admin}/routes")
        assert {r["name"] for r in routes2} == {r["name"] for r in routes}
        assert not hasattr(gw.table.match("/m/x"), "bandit")

        # Bad feedback is rejected: out-of-range reward, a service that
        # is not a variant of the route, an unknown route.
        for path, payload, want in (
            ("m", {"service": f"127.0.0.1:{a.port}", "reward": 2.0}, 400),
            ("m", {"service": "typo:8500", "reward": 0.5}, 400),
            ("ghost", {"service": f"127.0.0.1:{a.port}",
                       "reward": 0.5}, 404),
        ):
            try:
                code, _out, _ = http(
                    "POST", f"{admin}/routes/{path}/feedback", payload)
            except urllib.error.HTTPError as e:
                code = e.code
            assert code == want, (path, payload, code)
    finally:
        gw.stop()
        a.close()
        b.close()


def test_shadow_mirror_through_gateway(api):
    """Shadow traffic: the mirror backend sees every request (marked
    X-Shadow) but the client only ever sees the primary's response; a
    dead shadow is invisible to the client."""
    import time

    from kubeflow_tpu.gateway import Route

    primary, shadow = _IdentityBackend("primary"), _IdentityBackend("shadow")
    table = RouteTable()
    table.set_routes([Route(
        name="m", prefix="/m/",
        service=f"127.0.0.1:{primary.port}",
        shadow=f"127.0.0.1:{shadow.port}",
    )])
    gw = Gateway(table, port=0, admin_port=0)
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"
        _, out, _ = http("POST", f"{base}/m/predict", {"x": 1})
        assert out["variant"] == "primary"
        for _ in range(50):  # mirror is async
            if shadow.requests:
                break
            time.sleep(0.05)
        assert shadow.requests and shadow.requests[0]["shadow"] == "true"
        assert primary.requests[0]["shadow"] == ""

        # Dead shadow: the client path is unaffected.
        shadow.close()
        _, out, _ = http("POST", f"{base}/m/predict", {"x": 2})
        assert out["variant"] == "primary"
    finally:
        gw.stop()
        primary.close()


# ---------------------------------------------------------------------------
# Upstream health + circuit breaking (VERDICT r3 #8)
# ---------------------------------------------------------------------------


def test_upstream_health_eject_halfopen_recover():
    """The circuit state machine in isolation: threshold ejection,
    half-open single trial, doubled re-ejection backoff, full recovery."""
    from kubeflow_tpu.gateway import UpstreamHealth

    now = [0.0]
    h = UpstreamHealth(failure_threshold=3, ejection_seconds=10,
                       clock=lambda: now[0])
    svc = "m.kubeflow:8500"
    assert h.admits(svc)
    for _ in range(3):
        h.record_failure(svc)
    assert not h.admits(svc)                       # ejected
    assert h.filter_healthy([svc, "other"]) == ["other"]
    assert h.filter_healthy([svc]) == [svc]        # fail open when alone

    now[0] = 11
    assert h.admits(svc)                           # eligible for a trial
    h.begin_trial(svc)                             # ...consumed on route
    assert not h.admits(svc)                       # only ONE trial
    h.record_failure(svc)                          # trial failed
    assert not h.admits(svc)
    now[0] = 22                                    # 10s would have passed
    assert not h.admits(svc)                       # backoff doubled (20s)
    now[0] = 32
    assert h.admits(svc)
    h.begin_trial(svc)
    h.record_success(svc)                          # trial succeeded
    assert h.admits(svc) and h.admits(svc)         # circuit closed
    snap = h.snapshot()[svc]
    assert snap["healthy"] and snap["ejections"] == 0
    # An abandoned trial (e.g. tunnel path) expires instead of wedging.
    for _ in range(3):
        h.record_failure(svc)
    now[0] = 60
    h.begin_trial(svc)
    assert not h.admits(svc)
    now[0] = 95                                    # > TRIAL_TIMEOUT later
    assert h.admits(svc)


def test_traffic_shifts_on_upstream_death_and_returns(api):
    """VERDICT r3 #8's done-criterion: kill one of two variants — traffic
    shifts to the survivor within one probe interval (no client sees the
    corpse once ejected; the first hit that discovers it retries under
    the idempotent budget) — then returns after recovery."""
    import random
    import time

    from kubeflow_tpu.gateway import Gateway, Route, RouteTable

    import socket as socket_mod

    a, b = _IdentityBackend("a"), _IdentityBackend("b")
    table = RouteTable()
    table.set_routes([Route(
        name="m", prefix="/m/", service=f"127.0.0.1:{a.port}",
        backends=((f"127.0.0.1:{a.port}", 1), (f"127.0.0.1:{b.port}", 1)),
    )])
    with socket_mod.socket() as s_:
        s_.bind(("127.0.0.1", 0))
        admin_port = s_.getsockname()[1]
    gw = Gateway(table, port=0, admin_port=admin_port, probe_interval=0.2,
                 rng=random.Random(5))
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"

        def hit():
            code, out, _ = http("GET", f"{base}/m/x")
            return code, out["variant"]

        servers = {hit()[1] for _ in range(20)}
        assert servers == {"a", "b"}  # both healthy, both picked

        b_port = b.port
        b.close()  # the variant dies
        # Within one probe interval the prober ejects it; every request
        # afterwards lands on the survivor with status 200 (the one that
        # races the discovery retries onto the survivor).
        deadline = time.time() + 5
        while time.time() < deadline:
            if not gw.health.snapshot().get(
                    f"127.0.0.1:{b_port}", {}).get("healthy", True):
                break
            time.sleep(0.05)
        snap = gw.health.snapshot()[f"127.0.0.1:{b_port}"]
        assert not snap["healthy"], snap
        results = [hit() for _ in range(20)]
        assert all(code == 200 and srv == "a" for code, srv in results), \
            results

        # Admin surface exposes the ejection.
        code, out, _ = http(
            "GET",
            f"http://127.0.0.1:{admin_port}/upstreams")
        assert code == 200
        assert not out[f"127.0.0.1:{b_port}"]["healthy"]

        # Recovery: a new backend on the SAME port rejoins the pick set
        # after the prober's next pass + half-open success.
        b2 = _IdentityBackend("b", port=b_port)
        try:
            deadline = time.time() + 10
            seen = set()
            while time.time() < deadline and "b" not in seen:
                seen.add(hit()[1])
                time.sleep(0.05)
            assert seen == {"a", "b"}
        finally:
            b2.close()
    finally:
        gw.stop()
        a.close()


# ---------------------------------------------------------------------------
# Outlier-detector route (VERDICT r3 #7)
# ---------------------------------------------------------------------------


def test_outlier_route_flags_injected_anomalies(api):
    """The seldon outlier-detector surface: normal prediction traffic
    builds the baseline; an injected anomalous payload is tagged on the
    response and counted into the route's outlier rate."""
    import random

    from kubeflow_tpu.gateway import Gateway, RouteTable
    from kubeflow_tpu.manifests.core import generate

    backend = _IdentityBackend("m")
    svc = generate("serving-route", {
        "name": "bert", "outlier_threshold": 3.0, "outlier_window": 50,
    })[0]
    api.apply(svc)
    table = RouteTable()
    table.refresh(api)
    route = table.match("/models/bert/x")
    assert route.outlier_threshold == 3.0

    gw = Gateway(table, port=0, admin_port=0, probe_interval=0,
                 resolve=lambda a: f"127.0.0.1:{backend.port}",
                 rng=random.Random(3))
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"

        def predict(values):
            code, _out, headers = http(
                "POST", f"{base}/models/bert/v1/models/bert:predict",
                payload={"instances": [values]},
            )
            return code, headers

        rng = random.Random(0)
        for _ in range(30):  # baseline: values around 1.0
            code, headers = predict(
                [1.0 + rng.uniform(-0.1, 0.1) for _ in range(8)])
            assert code == 200
            assert headers["X-Outlier"] == "false"

        # The anomaly: two orders of magnitude off the baseline.
        code, headers = predict([400.0] * 8)
        assert code == 200
        assert headers["X-Outlier"] == "true"
        assert float(headers["X-Outlier-Score"]) > 3.0

        # Outliers don't poison the baseline: normal traffic is still
        # normal afterwards.
        code, headers = predict([1.0] * 8)
        assert headers["X-Outlier"] == "false"

        stats = gw.outliers.snapshot("bert-route")
        assert stats["outliers"] == 1 and stats["scored"] == 32
        assert stats["rate"] == pytest.approx(1 / 32, abs=1e-3)
    finally:
        gw.stop()
        backend.close()


def test_malformed_client_content_length_is_400():
    """ADVICE r5 #4: `int()` on a malformed client Content-Length used
    to kill the handler thread — no response, dropped connection. The
    gateway must answer 400 and keep serving."""
    import socket
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from kubeflow_tpu.gateway import Route

    class Echo(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            body = b'{"ok": true}'
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    backend = ThreadingHTTPServer(("127.0.0.1", 0), Echo)
    threading.Thread(target=backend.serve_forever, daemon=True).start()
    table = RouteTable()
    table.set_routes([Route(
        name="m", prefix="/m/",
        service=f"127.0.0.1:{backend.server_address[1]}")])
    gw = Gateway(table, port=0, admin_port=0)
    gw.start()
    try:
        port = gw._proxy.server_address[1]
        client = socket.create_connection(("127.0.0.1", port), timeout=10)
        client.sendall((
            f"POST /m/x HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            "Content-Length: abc\r\n\r\n").encode())
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = client.recv(4096)
            if not chunk:
                break
            resp += chunk
        assert b" 400 " in resp.split(b"\r\n", 1)[0] + b" ", resp
        assert b"malformed Content-Length" in resp + client.recv(4096)
        client.close()
        # The handler thread survived: a well-formed request still flows.
        status, body, _ = http("POST", f"http://127.0.0.1:{port}/m/x",
                               {"a": 1})
        assert status == 200 and body == {"ok": True}
        assert gw.errors_total >= 1
    finally:
        gw.stop()
        backend.shutdown()


def test_malformed_upstream_content_length_is_502():
    """ADVICE r5 #4, upstream side: a backend advertising
    `Content-Length: banana` must surface as a clean 502 — the parse
    happens BEFORE the status line goes out, so the client sees a real
    response, not a half-written 200."""
    import socket

    from kubeflow_tpu.gateway import Route

    class RawBackend:
        def __init__(self):
            self.sock = socket.socket()
            self.sock.bind(("127.0.0.1", 0))
            self.sock.listen(8)
            self.port = self.sock.getsockname()[1]
            threading.Thread(target=self._serve, daemon=True).start()

        def _serve(self):
            while True:
                try:
                    conn, _ = self.sock.accept()
                except OSError:
                    return
                threading.Thread(target=self._session, args=(conn,),
                                 daemon=True).start()

        def _session(self, conn):
            try:
                data = b""
                while b"\r\n\r\n" not in data:
                    chunk = conn.recv(4096)
                    if not chunk:
                        break
                    data += chunk
                conn.sendall(b"HTTP/1.1 200 OK\r\n"
                             b"Content-Length: banana\r\n\r\nhello")
                conn.close()
            except OSError:
                pass

        def close(self):
            self.sock.close()

    backend = RawBackend()
    table = RouteTable()
    table.set_routes([Route(name="u", prefix="/u/",
                            service=f"127.0.0.1:{backend.port}")])
    gw = Gateway(table, port=0, admin_port=0)
    gw.start()
    try:
        port = gw._proxy.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/u/x",
                                   timeout=10)
        assert e.value.code == 502
        assert "malformed upstream" in json.loads(e.value.read())["error"]
        assert gw.errors_total >= 1
    finally:
        gw.stop()
        backend.close()


def test_request_id_generated_preserved_echoed_forwarded():
    """Observability satellite: the gateway's X-Request-ID contract over
    raw sockets — generated when the client sent none, preserved when
    present, echoed exactly once on the response, and forwarded to the
    upstream."""
    import socket
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from kubeflow_tpu.gateway import Route

    seen_ids = []

    class Capture(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            seen_ids.append(self.headers.get("X-Request-ID"))
            body = b'{"ok": true}'
            self.send_response(200)
            # The upstream echoes the id too (the model server does);
            # the gateway must de-duplicate, not relay a second copy.
            self.send_header("X-Request-ID",
                             self.headers.get("X-Request-ID", ""))
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    backend = ThreadingHTTPServer(("127.0.0.1", 0), Capture)
    threading.Thread(target=backend.serve_forever, daemon=True).start()
    table = RouteTable()
    table.set_routes([Route(
        name="m", prefix="/m/",
        service=f"127.0.0.1:{backend.server_address[1]}")])
    gw = Gateway(table, port=0, admin_port=0)
    gw.start()

    def raw_get(extra_header=""):
        port = gw._proxy.server_address[1]
        client = socket.create_connection(("127.0.0.1", port), timeout=10)
        client.sendall((
            f"GET /m/x HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            f"{extra_header}Connection: close\r\n\r\n").encode())
        resp = b""
        while True:
            chunk = client.recv(4096)
            if not chunk:
                break
            resp += chunk
        client.close()
        head = resp.split(b"\r\n\r\n", 1)[0].decode()
        rid_lines = [ln.split(":", 1)[1].strip()
                     for ln in head.split("\r\n")
                     if ln.lower().startswith("x-request-id:")]
        return head, rid_lines

    try:
        # Absent → generated: response carries exactly one non-empty id,
        # and it is the same id the upstream received.
        _head, rids = raw_get()
        assert len(rids) == 1 and rids[0], rids
        assert seen_ids == [rids[0]]

        # Present → preserved verbatim, echoed, forwarded.
        _head, rids = raw_get("X-Request-ID: client-chosen-42\r\n")
        assert rids == ["client-chosen-42"]
        assert seen_ids[-1] == "client-chosen-42"

        # The gateway's own (non-proxied) responses echo too.
        _head, rids = raw_get("X-Request-ID: health-7\r\n")
        assert rids == ["health-7"]
    finally:
        gw.stop()
        backend.shutdown()


def test_single_request_traced_gateway_server_decoder(platform):
    """Acceptance criterion: one request through gateway → model server
    → decoder yields ONE request id everywhere, and the decoder
    timeline's span sum matches the observed end-to-end latency within
    measurement noise."""
    import time

    _api, gw, base = platform
    payload = {"instances": [{"tokens": [5, 6, 7], "max_new_tokens": 6}]}
    url = f"{base}/models/lm/v1/models/lm-test-tiny:predict"

    # Warm-up: first contact builds + compiles the decoder (outside any
    # timeline); the measured request then isolates serving latency.
    http("POST", url, payload)

    rid = "trace-e2e-0001"
    t0 = time.perf_counter()
    code, out, headers = http("POST", url, payload,
                              headers={"X-Request-ID": rid})
    e2e_ms = 1e3 * (time.perf_counter() - t0)
    assert code == 200 and len(out["predictions"][0]["tokens"]) == 6
    assert headers["X-Request-ID"] == rid  # echoed through the gateway

    # The decoder's timeline, fetched THROUGH the gateway (the one-curl
    # contract): same id, closed, full lifecycle.
    code, dbg, _ = http("GET",
                        f"{base}/models/lm/debug/requests?id={rid}")
    assert code == 200
    recs = dbg["requests"]
    assert len(recs) == 1, recs
    rec = recs[0]
    assert rec["request_id"] == rid and rec["status"] == "length"
    names = [e["name"] for e in rec["events"]]
    for expected in ("submit", "queued", "admitted", "prefill",
                     "first_token", "finish"):
        assert expected in names, (expected, names)

    # Span sum == timeline duration (by construction) and within
    # measurement noise of the observed end-to-end latency: the decoder
    # window nests inside the client's, short only of HTTP/proxy
    # overhead.
    span_sum_ms = sum(s["duration_ms"] for s in rec["spans"])
    assert span_sum_ms == pytest.approx(rec["duration_ms"], abs=0.05)
    assert span_sum_ms <= e2e_ms + 1.0
    assert e2e_ms - span_sum_ms <= max(0.5 * e2e_ms, 150.0), (
        e2e_ms, span_sum_ms)

    # The gateway hop recorded the same id on its own timeline.
    gw_recs = gw.trace.find(rid)
    assert gw_recs and all(r["status"] != "open" for r in gw_recs)


def test_kv_fill_cache_staleness_and_no_signal_semantics():
    """The gateway's KV-fill scrape: fresh values serve from cache, a
    stale value serves WHILE one background refresh runs, and a backend
    that cannot be scraped yields None (signal unavailable) — never
    0.0 (an empty pool it might not have)."""
    import time as _time

    from kubeflow_tpu.gateway.resilience import KvFillCache

    clock = {"t": 0.0}
    fills = {"b1": 0.9}

    def fetch(addr):
        return fills.get(addr)

    cache = KvFillCache(ttl=5.0, fetch=fetch, clock=lambda: clock["t"])

    def settle(service, deadline=5.0):
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < deadline:
            with cache._lock:
                if not cache._cells[service]["refreshing"]:
                    return
            _time.sleep(0.01)
        raise AssertionError("refresh never settled")

    # Never scraped: no signal yet, but the miss kicks a refresh.
    assert cache.fill("b1") is None
    settle("b1")
    assert cache.fill("b1") == 0.9          # fresh → cached value
    assert cache.scrapes == 1
    # Within ttl: served from cache, no second scrape.
    clock["t"] += 2
    assert cache.fill("b1") == 0.9
    assert cache.scrapes == 1
    # Past ttl: the STALE value serves immediately; the background
    # refresh picks up the new truth.
    clock["t"] += 10
    fills["b1"] = 0.2
    assert cache.fill("b1") == 0.9
    settle("b1")
    assert cache.fill("b1") == 0.2
    # Backend goes unscrapeable: inside the grace window the last value
    # serves; past it the signal goes dark (None), never 0.0.
    fills.pop("b1")
    clock["t"] += 10
    assert cache.fill("b1") == 0.2
    settle("b1")
    clock["t"] += 11  # past 2x ttl grace
    cache.fill("b1")
    settle("b1")
    assert cache.fill("b1") is None
    assert cache.scrape_failures >= 1
    # A backend that never answered: always None.
    assert cache.fill("b2") is None
    settle("b2")
    assert cache.fill("b2") is None


def test_affine_kv_pressure_spills_to_less_full_backend(api):
    """Gateway-side KV pressure: the affine pick spills when the
    target's scraped pool fill crosses kv_pressure AND a less-full
    backend exists; an unscrapeable target (no signal) never spills."""
    from kubeflow_tpu.manifests.core import gateway_route

    a, b = _IdentityBackend("a"), _IdentityBackend("b")
    svc = {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "name": "pool", "namespace": "kubeflow",
            "annotations": gateway_route(
                "pool", "/models/m/", "m-r0.kubeflow:8500",
                backends=[{"service": "m-r0.kubeflow:8500", "weight": 1},
                          {"service": "m-r1.kubeflow:8500", "weight": 1}],
                strategy="prefix-affine", affinity_tokens=4,
                pressure=0, kv_pressure=0.8),
        },
    }
    api.apply(svc)
    table = RouteTable()
    assert table.refresh(api) == 1
    route = table.match("/models/m/x")
    assert route.kv_pressure == 0.8
    backends = {
        "m-r0.kubeflow:8500": f"127.0.0.1:{a.port}",
        "m-r1.kubeflow:8500": f"127.0.0.1:{b.port}",
    }
    gw = Gateway(table, port=0, admin_port=0, probe_interval=0,
                 resolve=lambda addr: backends.get(addr, addr))
    fills: dict = {}

    class _StubFill:
        scrapes = 0
        scrape_failures = 0

        def fill(self, service, resolve=None):
            return fills.get(service)

        def snapshot(self):
            return dict(fills)

    gw.kv_fill = _StubFill()
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"

        def predict(tokens):
            _, out, _ = http(
                "POST", f"{base}/models/m/v1/models/m:predict",
                {"instances": [{"tokens": tokens}]})
            return out["variant"]

        toks = [1, 2, 3, 4]
        home = predict(toks)
        other = "b" if home == "a" else "a"
        home_svc = ("m-r0.kubeflow:8500" if home == "a"
                    else "m-r1.kubeflow:8500")
        other_svc = ("m-r0.kubeflow:8500" if other == "a"
                     else "m-r1.kubeflow:8500")
        # No signal anywhere: no spill (None is never "empty").
        assert predict(toks) == home
        assert gw.affine_spills == 0
        # Affine target over the bound, spill target less full → spill.
        fills[home_svc] = 0.95
        fills[other_svc] = 0.3
        assert predict(toks) == other
        assert gw.affine_spills == 1
        # Spill target just as full → stay home (nowhere better).
        fills[other_svc] = 0.97
        assert predict(toks) == home
        # Pressure relieved → the key returns home (no sticky spill).
        fills[home_svc] = 0.2
        fills[other_svc] = 0.3
        assert predict(toks) == home
    finally:
        gw.stop()
        for be in (a, b):
            be.close()


def test_prefix_affine_routing_through_gateway(api):
    """Replica-pool routing e2e: a prefix-affine route over two live
    backends sends every request sharing a prompt prefix to ONE backend
    (rendezvous by the leading tokens), spreads distinct prefixes, and
    remaps ONLY the dead backend's keys when a replica dies — while the
    health machinery 502s the dead pick and then ejects it."""
    from kubeflow_tpu.gateway.resilience import UpstreamHealth
    from kubeflow_tpu.manifests.core import gateway_route

    a, b = _IdentityBackend("a"), _IdentityBackend("b")
    svc = {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "name": "pool", "namespace": "kubeflow",
            "annotations": gateway_route(
                "pool", "/models/m/", "m-r0.kubeflow:8500",
                backends=[{"service": "m-r0.kubeflow:8500", "weight": 1},
                          {"service": "m-r1.kubeflow:8500", "weight": 1}],
                strategy="prefix-affine", affinity_tokens=4, pressure=0),
        },
    }
    api.apply(svc)
    table = RouteTable()
    assert table.refresh(api) == 1
    backends = {
        "m-r0.kubeflow:8500": f"127.0.0.1:{a.port}",
        "m-r1.kubeflow:8500": f"127.0.0.1:{b.port}",
    }
    gw = Gateway(table, port=0, admin_port=0, probe_interval=0,
                 resolve=lambda addr: backends.get(addr, addr),
                 health=UpstreamHealth(failure_threshold=1,
                                       ejection_seconds=30.0))
    gw.start()
    try:
        base = f"http://127.0.0.1:{gw._proxy.server_address[1]}"

        def predict(tokens):
            _, out, _ = http(
                "POST", f"{base}/models/m/v1/models/m:predict",
                {"instances": [{"tokens": tokens}]})
            return out["variant"]

        # Affinity: one prompt prefix → one backend, every time.
        group1 = [predict([1, 2, 3, 4, 9 + i]) for i in range(6)]
        assert len(set(group1)) == 1
        # Distinct prefixes spread over the pool.
        variants = {predict([seed, seed + 1, 5, 6]) for seed in range(16)}
        assert variants == {"a", "b"}

        # Find a prefix homed on each backend, then kill backend
        # group1 lives on.
        home1 = group1[0]
        other_tokens = next(
            [seed, seed + 1, 5, 6] for seed in range(16)
            if predict([seed, seed + 1, 5, 6]) != home1)
        victim = a if home1 == "a" else b
        survivor = "b" if home1 == "a" else "a"
        victim.close()

        # First request after death: connect fails → 502 (POST bodies
        # are never retried blind), and the failure ejects the backend.
        with pytest.raises(urllib.error.HTTPError) as e:
            predict([1, 2, 3, 4, 99])
        assert e.value.code == 502
        # Dead backend ejected → its keys remap to the survivor...
        assert predict([1, 2, 3, 4, 100]) == survivor
        # ...while keys whose affine home SURVIVED stay exactly where
        # they were (only the dead replica's keys moved).
        for _ in range(3):
            assert predict(other_tokens) == survivor
    finally:
        gw.stop()
        for be in (a, b):
            try:
                be.close()
            except Exception:
                pass


class _DigestBackend:
    """HTTP backend that reads its POST body fully and answers with its
    own name plus the body's length and sha256 — proof an upstream
    received a (possibly gateway-streamed) body byte-identically."""

    def __init__(self, name):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.name = name
        outer = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                data = b""
                while len(data) < n:
                    chunk = self.rfile.read(n - len(data))
                    if not chunk:
                        break
                    data += chunk
                body = json.dumps({
                    "variant": outer.name,
                    "len": len(data),
                    "sha": hashlib.sha256(data).hexdigest(),
                }).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_long_body_spills_past_affinity_head():
    """Long-context regression: a prefix-affine route used to buffer
    (and json-parse) the ENTIRE request body just to compute the
    affinity key. A multi-megabyte prompt must instead hash a bounded
    head, land on the SAME affine replica a short prompt with the same
    leading tokens does, and stream through to the backend intact."""
    from kubeflow_tpu.gateway import Route

    a, b = _DigestBackend("a"), _DigestBackend("b")
    table = RouteTable()
    table.set_routes([Route(
        name="long", prefix="/long/",
        service=f"127.0.0.1:{a.port}",
        backends=((f"127.0.0.1:{a.port}", 1),
                  (f"127.0.0.1:{b.port}", 1)),
        strategy="prefix-affine")])
    gw = Gateway(table, port=0, admin_port=0, probe_interval=0)
    gw.start()
    try:
        port = gw._proxy.server_address[1]
        toks = [7, 11, 13, 17, 19, 23]
        # Short prompt: the strict-parse affinity path.
        status, short_reply, _ = http(
            "POST", f"http://127.0.0.1:{port}/long/x:predict",
            {"instances": [{"tokens": toks}]})
        assert status == 200
        # Long prompt, same leading tokens: ~1 MiB of payload after the
        # token array, far past the gateway's affinity head bound.
        long_body = (
            b'{"instances": [{"tokens": '
            + json.dumps(toks).encode()
            + b', "pad": "' + b"x" * (1 << 20) + b'"}]}')
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/long/x:predict", data=long_body,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            long_reply = json.loads(resp.read())
        # Byte-identical arrival despite the spill...
        assert long_reply["len"] == len(long_body)
        assert long_reply["sha"] == \
            hashlib.sha256(long_body).hexdigest()
        # ...on the SAME affine replica the short prompt routed to (the
        # truncated-head token extraction must agree with full parsing).
        assert long_reply["variant"] == short_reply["variant"]
        # Unparseable long bodies still route deterministically (digest
        # fallback over the head): same garbage, same backend.
        junk = b"\x00\x01" * (1 << 19)
        picks = set()
        for _ in range(2):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/long/x:predict", data=junk,
                method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                picks.add(json.loads(resp.read())["variant"])
        assert len(picks) == 1
    finally:
        gw.stop()
        a.close()
        b.close()


def test_max_body_bytes_rejects_oversized_declared_body():
    """A declared Content-Length beyond ``max_body_bytes`` answers 413
    BEFORE the gateway reads a single body byte — sent raw so the test
    controls exactly what goes on the wire (headers only, no body)."""
    import socket

    from kubeflow_tpu.gateway import Route

    be = _DigestBackend("a")
    table = RouteTable()
    table.set_routes([Route(
        name="cap", prefix="/cap/",
        service=f"127.0.0.1:{be.port}")])
    gw = Gateway(table, port=0, admin_port=0, probe_interval=0,
                 max_body_bytes=1 << 20)
    gw.start()
    try:
        port = gw._proxy.server_address[1]
        client = socket.create_connection(("127.0.0.1", port),
                                          timeout=10)
        # Declare 64 MiB; send NOTHING after the headers. The gateway
        # must answer from the header alone (buffering first would hang
        # this test until timeout).
        client.sendall((
            f"POST /cap/x HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            f"Content-Length: {64 << 20}\r\n\r\n").encode())
        resp = b""
        while b"\r\n\r\n" not in resp:
            chunk = client.recv(4096)
            if not chunk:
                break
            resp += chunk
        assert b" 413 " in resp.split(b"\r\n", 1)[0] + b" ", resp
        assert b"max_body_bytes" in resp + client.recv(4096)
        client.close()
        assert gw.body_rejected_total == 1
        # Within the cap still flows end-to-end.
        status, body, _ = http(
            "POST", f"http://127.0.0.1:{port}/cap/x", {"a": 1})
        assert status == 200 and body["len"] > 0
    finally:
        gw.stop()
        be.close()
