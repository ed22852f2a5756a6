"""Mutating admission + CRD conversion webhook:
`python -m kubeflow_tpu.auth.webhook`.

The gcp-admission-webhook analogue (components/gcp-admission-webhook/
main.go:131-158, patch ops :51-53): pods labeled
`kubeflow-tpu.org/cred-secret=<name>` get that Secret mounted plus
GOOGLE_APPLICATION_CREDENTIALS pointed at it (the credentials-pod-preset
surface); TPU-requesting containers get safe env defaults. Speaks the
AdmissionReview v1 protocol on POST /mutate, and the ConversionReview
v1 protocol on POST /convert — the structural converter a REAL
apiserver calls for the job CRDs' multi-version story (the fake
apiserver converts in-process with the same registered functions).
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import ssl
import sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from kubeflow_tpu.runtime import strip_glog_args

CRED_LABEL = "kubeflow-tpu.org/cred-secret"
CRED_MOUNT_PATH = "/var/secrets/platform"
CRED_VOLUME = "platform-creds"
TPU_RESOURCE = "google.com/tpu"


def _env_patch(container: dict, idx: int, name: str, value: str) -> list[dict]:
    existing = container.get("env")
    entry = {"name": name, "value": value}
    if existing is None:
        return [{"op": "add", "path": f"/spec/containers/{idx}/env",
                 "value": [entry]}]
    if any(e.get("name") == name for e in existing):
        return []
    return [{"op": "add", "path": f"/spec/containers/{idx}/env/-",
             "value": entry}]


def mutate_pod(pod: dict) -> list[dict]:
    """JSONPatch ops for one pod (empty = no mutation)."""
    patches: list[dict] = []
    spec = pod.get("spec", {})
    containers = spec.get("containers", [])
    secret = pod.get("metadata", {}).get("labels", {}).get(CRED_LABEL)

    if secret:
        volumes = spec.get("volumes")
        vol = {"name": CRED_VOLUME, "secret": {"secretName": secret}}
        if volumes is None:
            patches.append({"op": "add", "path": "/spec/volumes",
                            "value": [vol]})
        elif not any(v.get("name") == CRED_VOLUME for v in volumes):
            patches.append({"op": "add", "path": "/spec/volumes/-",
                            "value": vol})
        for i, c in enumerate(containers):
            mounts = c.get("volumeMounts")
            mount = {"name": CRED_VOLUME, "mountPath": CRED_MOUNT_PATH,
                     "readOnly": True}
            if mounts is None:
                patches.append({
                    "op": "add",
                    "path": f"/spec/containers/{i}/volumeMounts",
                    "value": [mount],
                })
            elif not any(m.get("name") == CRED_VOLUME for m in mounts):
                patches.append({
                    "op": "add",
                    "path": f"/spec/containers/{i}/volumeMounts/-",
                    "value": mount,
                })
            patches.extend(_env_patch(
                c, i, "GOOGLE_APPLICATION_CREDENTIALS",
                f"{CRED_MOUNT_PATH}/key.json",
            ))

    # TPU env defaults for containers requesting chips.
    for i, c in enumerate(containers):
        limits = c.get("resources", {}).get("limits", {})
        if TPU_RESOURCE in limits:
            patches.extend(_env_patch(c, i, "TPU_MIN_LOG_LEVEL", "1"))
            # A pod that asked for chips runs on them or fails at
            # start-up; "tpu,cpu" would let it train on the host CPU and
            # look healthy.
            patches.extend(_env_patch(c, i, "JAX_PLATFORMS", "tpu"))
    return patches


def review_response(review: dict) -> dict:
    """AdmissionReview request → AdmissionReview response."""
    request = review.get("request", {})
    uid = request.get("uid", "")
    obj = request.get("object", {}) or {}
    response: dict = {"uid": uid, "allowed": True}
    if obj.get("kind", "Pod") == "Pod":
        patches = mutate_pod(obj)
        if patches:
            response["patchType"] = "JSONPatch"
            response["patch"] = base64.b64encode(
                json.dumps(patches).encode()
            ).decode()
    return {
        "apiVersion": review.get("apiVersion",
                                 "admission.k8s.io/v1"),
        "kind": "AdmissionReview",
        "response": response,
    }


def convert_response(review: dict) -> dict:
    """ConversionReview request → response, via the converters the API
    packages register with the client layer (apis/jobs.convert_job)."""
    # Importing the API packages registers their converters.
    from kubeflow_tpu.apis import jobs as _jobs  # noqa: F401
    from kubeflow_tpu.k8s.client import ApiError, KindRegistry

    request = review.get("request") or {}
    if not isinstance(request, dict):
        request = {}
    uid = request.get("uid", "")
    desired = request.get("desiredAPIVersion", "")
    converted, failure = [], None
    for obj in request.get("objects") or []:
        if not isinstance(obj, dict):
            # Malformed input must produce the protocol's Failed result,
            # not a handler crash and a dropped connection.
            failure = "objects entries must be objects"
            break
        try:
            converted.append(KindRegistry.convert(obj, desired))
        except ApiError as e:
            failure = e.message or str(e)
            break
    response: dict = {"uid": uid}
    if failure is None:
        response["result"] = {"status": "Success"}
        response["convertedObjects"] = converted
    else:
        response["result"] = {"status": "Failed", "message": failure}
    return {
        "apiVersion": review.get("apiVersion",
                                 "apiextensions.k8s.io/v1"),
        "kind": "ConversionReview",
        "response": response,
    }


def make_server(port: int, *, certfile: str = "",
                keyfile: str = "") -> ThreadingHTTPServer:
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _send(self, code: int, payload: dict) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path in ("/healthz", "/readyz"):
                self._send(200, {"status": "ok"})
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            if self.path not in ("/mutate", "/convert"):
                self._send(404, {"error": "not found"})
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                review = json.loads(self.rfile.read(length) or b"{}")
                handler = (review_response if self.path == "/mutate"
                           else convert_response)
                self._send(200, handler(review))
            except (ValueError, KeyError) as e:
                self._send(400, {"error": str(e)})

    httpd = ThreadingHTTPServer(("0.0.0.0", port), Handler)
    if certfile and keyfile:
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(certfile, keyfile)
        httpd.socket = ctx.wrap_socket(httpd.socket, server_side=True)
    return httpd


def _mint_ca_and_leaf(namespace: str, service: str):
    """Generate a webhook serving CA + leaf for the Service DNS names.
    Returns (KeyCert ca, KeyCert leaf, base64 CA bundle)."""
    from kubeflow_tpu.auth import pki

    ca = pki.make_ca(f"{service}-ca.{namespace}")
    leaf = pki.issue(ca, [
        f"{service}.{namespace}.svc",
        f"{service}.{namespace}.svc.cluster.local",
        service,
    ], duration_seconds=365 * 24 * 3600)
    bundle = base64.b64encode(ca.cert_pem.encode()).decode()
    return ca, leaf, bundle


def self_sign(namespace: str, service: str = "admission-webhook"):
    """Generate a webhook serving CA + leaf for the Service DNS names.
    Returns (KeyCert leaf, base64 CA bundle)."""
    _ca, leaf, bundle = _mint_ca_and_leaf(namespace, service)
    return leaf, bundle


def ensure_shared_ca(client, namespace: str,
                     service: str = "admission-webhook",
                     secret_name: str = "admission-webhook-tls"):
    """Cluster-wide self-sign: ONE CA/leaf per deployment, not one per
    pod. With ``--self-sign`` and ``replicas > 1`` each pod used to mint
    its own CA and race :func:`patch_ca_bundles` — whichever pod patched
    last won the clientConfigs while its peers kept serving leaves from
    a different root, so a fraction of admission/conversion dials failed
    TLS verification forever. Persisting CA + leaf in a Secret makes the
    mint a cluster-wide once: every pod first loads the Secret; on miss
    it mints and ``create``s, and the apiserver's create-conflict (409)
    picks the single winner — losers throw their candidate away and load
    the winner's. Returns (KeyCert leaf, base64 CA bundle, created)."""
    from kubeflow_tpu.auth.pki import KeyCert
    from kubeflow_tpu.k8s.client import ApiError

    def _load(secret):
        data = secret.get("data", {}) or {}

        def field(key):
            return base64.b64decode(data.get(key, "")).decode()

        leaf = KeyCert(key_pem=field("tls.key"), cert_pem=field("tls.crt"),
                       ca_pem=field("ca.crt"))
        if not (leaf.key_pem and leaf.cert_pem and leaf.ca_pem):
            raise ValueError(
                f"secret {secret_name} is missing tls.key/tls.crt/ca.crt")
        return leaf, base64.b64encode(leaf.ca_pem.encode()).decode()

    existing = client.get_or_none("v1", "Secret", secret_name, namespace)
    if existing is not None:
        return (*_load(existing), False)
    ca, leaf, bundle = _mint_ca_and_leaf(namespace, service)
    secret = {
        "apiVersion": "v1",
        "kind": "Secret",
        "metadata": {"name": secret_name, "namespace": namespace,
                     "labels": {"app": service}},
        "type": "kubernetes.io/tls",
        "data": {
            "tls.crt": base64.b64encode(leaf.cert_pem.encode()).decode(),
            "tls.key": base64.b64encode(leaf.key_pem.encode()).decode(),
            "ca.crt": base64.b64encode(ca.cert_pem.encode()).decode(),
            # CA key rides along so a future rotation can re-issue
            # leaves under the SAME root without re-patching bundles.
            "ca.key": base64.b64encode(ca.key_pem.encode()).decode(),
        },
    }
    try:
        client.create(secret)
    except ApiError as e:
        if e.code != 409:
            raise
        # Lost the race: a peer pod created it between our get and
        # create. Its CA is the cluster's CA now — load it.
        return (*_load(client.get("v1", "Secret", secret_name, namespace)),
                False)
    return leaf, bundle, True


def patch_ca_bundles(client, ca_bundle_b64: str,
                     webhook_name: str = "admission-webhook"
                     ) -> tuple[int, int]:
    """Write the serving CA into every in-cluster clientConfig that dials
    this webhook: the MutatingWebhookConfiguration AND each job CRD's
    conversion stanza — the cert-manager-CA-injector role, done by the
    webhook itself (the manifest's `ca_bundle` param may stay empty).
    Returns (patched, failed); the caller retries while failed > 0 —
    CRD conversion has no failurePolicy escape, so a stale bundle must
    converge, not wait for a lucky restart. Network errors count as
    failures (requests exceptions are OSErrors), never crashes."""
    from kubeflow_tpu.apis.jobs import API_GROUP, PLURALS
    from kubeflow_tpu.k8s.client import ApiError

    patched, failed = 0, 0
    try:
        mwc = client.get_or_none(
            "admissionregistration.k8s.io/v1",
            "MutatingWebhookConfiguration", webhook_name)
        if mwc is not None:
            changed = False
            for wh in mwc.get("webhooks", []):
                cc = wh.setdefault("clientConfig", {})
                if cc.get("caBundle") != ca_bundle_b64:
                    cc["caBundle"] = ca_bundle_b64
                    changed = True
            if changed:
                client.update(mwc)
                patched += 1
    except (ApiError, OSError):
        failed += 1
    for plural in PLURALS.values():
        try:
            crd = client.get_or_none(
                "apiextensions.k8s.io/v1", "CustomResourceDefinition",
                f"{plural}.{API_GROUP}")
            if crd is None:
                continue
            webhook = (crd.get("spec", {}).get("conversion", {})
                       .get("webhook"))
            if webhook is None:
                continue
            cc = webhook.setdefault("clientConfig", {})
            if cc.get("caBundle") != ca_bundle_b64:
                cc["caBundle"] = ca_bundle_b64
                client.update(crd)
                patched += 1
        except (ApiError, OSError):
            failed += 1
    return patched, failed


def main(argv=None) -> int:
    argv = strip_glog_args(list(sys.argv[1:] if argv is None else argv))
    p = argparse.ArgumentParser(description="mutating admission webhook")
    p.add_argument("--port", type=int, default=8443)
    p.add_argument("--tls-cert", default="",
                   help="TLS cert path (with --tls-key; plain HTTP if "
                        "unset and --self-sign absent)")
    p.add_argument("--tls-key", default="")
    p.add_argument("--self-sign", action="store_true",
                   help="generate a serving CA + leaf at startup and "
                        "serve TLS with it")
    p.add_argument("--patch-ca", action="store_true",
                   help="write the serving CA into the in-cluster "
                        "MutatingWebhookConfiguration and job-CRD "
                        "conversion clientConfigs (requires --self-sign)")
    p.add_argument("--pod-namespace",
                   default=os.environ.get("POD_NAMESPACE", ""),
                   help="namespace for self-signed Service DNS names "
                        "(default: POD_NAMESPACE env, else --namespace)")
    p.add_argument("--patch-retry-seconds", type=float, default=30.0,
                   help="retry cadence while any caBundle patch is "
                        "failing (CRD conversion has no failurePolicy "
                        "escape — the bundle must converge)")
    from kubeflow_tpu.runtime import add_client_args, client_from_args

    add_client_args(p)  # --apiserver/--token-path/--namespace (in-cluster aware)
    args = p.parse_args(argv)

    certfile, keyfile = args.tls_cert, args.tls_key
    bundle = ""
    client = None
    ca_secret_shared = False
    if args.self_sign:
        import tempfile

        ns = args.pod_namespace or args.namespace
        if args.patch_ca:
            # Replicated deployments MUST share one CA: per-pod minting
            # races patch_ca_bundles and strands peers on an unpatched
            # root. First writer persists CA+leaf in a Secret
            # (create-conflict picks the winner); everyone else loads.
            client = client_from_args(args)
            try:
                leaf, bundle, _created = ensure_shared_ca(client, ns)
                ca_secret_shared = True
            except (OSError, ValueError) as e:
                # Secret API unreachable at boot: fall back to a local
                # mint so the pod comes up; the patch retry loop keeps
                # converging the bundle.
                print(json.dumps({"msg": "shared-CA secret unavailable, "
                                         "self-signing locally",
                                  "error": str(e)}), flush=True)
                leaf, bundle = self_sign(ns)
        else:
            leaf, bundle = self_sign(ns)
        cert_f = tempfile.NamedTemporaryFile("w", suffix=".pem",
                                             delete=False)
        cert_f.write(leaf.chain_pem)
        cert_f.close()
        key_f = tempfile.NamedTemporaryFile("w", suffix=".pem",
                                            delete=False)
        key_f.write(leaf.key_pem)
        key_f.close()
        certfile, keyfile = cert_f.name, key_f.name

    httpd = make_server(args.port, certfile=certfile, keyfile=keyfile)
    if args.self_sign:
        # The SSLContext holds the loaded chain; don't leave key
        # material on disk for the container lifetime.
        for path in (certfile, keyfile):
            try:
                os.unlink(path)
            except OSError:
                pass
    patched = failed = 0
    if args.patch_ca and bundle:
        if client is None:
            client = client_from_args(args)
        patched, failed = patch_ca_bundles(client, bundle)
        if failed:
            import threading

            def retry_loop():
                while True:
                    import time as _time

                    _time.sleep(args.patch_retry_seconds)
                    _p, f = patch_ca_bundles(client, bundle)
                    if f == 0:
                        return

            threading.Thread(target=retry_loop, daemon=True).start()

    print(json.dumps({"msg": "admission webhook up", "port": args.port,
                      "tls": bool(certfile),
                      "self_signed": args.self_sign,
                      "ca_secret_shared": ca_secret_shared,
                      "ca_bundles_patched": patched,
                      "ca_patches_failed": failed}), flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
