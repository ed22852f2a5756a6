"""Flash-crowd elasticity tests: sub-second replica birth.

Four surfaces, matching docs/serving.md "Cold start & flash crowds":

- **CompileCache** (serving/compile_cache.py): the engine fingerprint
  is stable for identical configs and splits on any knob, the dispatch
  keys cover exactly the decoder's executable set, the manifest merges
  atomically and a torn manifest reads as empty (a birth must compile,
  never crash), and hit/miss accounting matches what a second
  same-fingerprint replica would reuse.

- **Warming health** (satellite: /healthz): a booting server answers
  ``{"status": "warming"}`` on a RAW socket — no client library, the
  exact bytes a gateway probe sends — for the whole warm window, then
  flips to ``ok``; the gateway's UpstreamHealth treats warming as
  route-excluded-but-not-dead (no failure counters, no ejection, no
  half-open walk on exit).

- **Donor fallback** (satellite: donor death mid-pull): a newborn
  walks its donor list — dead donor, then a donor that dies MID-pull
  after serving a real first chunk, then a live one — and boots with
  the live donor's exact bytes at the donor's epoch; with every donor
  dead it falls back to the checkpoint byte-identically. The chunk
  assembler's complete-or-nothing rule means no partial epoch can
  ever install.

- **Fleet ramp** (DecoderFleet.add_replica): a warming newborn takes
  no affine share but sits in the spill pool; mark_warm rebalances by
  plain rendezvous; donor_for never offers a warming replica.
"""

from __future__ import annotations

import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import jax

from kubeflow_tpu.gateway.resilience import UpstreamHealth
from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.models.transformer import serving_params
from kubeflow_tpu.serving import weights as weights_mod
from kubeflow_tpu.serving.compile_cache import (
    CompileCache,
    dispatch_keys,
    engine_fingerprint,
)
from kubeflow_tpu.serving.engine import EngineConfig, InferenceEngine
from kubeflow_tpu.serving.fleet import DecoderFleet
from kubeflow_tpu.serving.server import ModelServer

SPEC = get_model("lm-test-tiny")
P_DONOR = SPEC.init(jax.random.PRNGKey(1), SPEC.config)


def _flat(params) -> dict:
    return {p: np.asarray(a)
            for p, a in weights_mod.flatten_params(params).items()}


def _held(params):
    """A float32 tree as a replica holds it (cast once, at install)."""
    return serving_params(params, SPEC.config)


def _trees_equal(a, b) -> bool:
    fa, fb = _flat(a), _flat(b)
    return fa.keys() == fb.keys() and all(
        np.array_equal(fa[k], fb[k]) for k in fa)


# ---------------------------------------------------------------------------
# CompileCache
# ---------------------------------------------------------------------------


def test_fingerprint_stable_and_config_sensitive():
    fp = engine_fingerprint(SPEC.config, tp=1, kv_layout="paged",
                            slots=4)
    assert fp == engine_fingerprint(SPEC.config, tp=1,
                                    kv_layout="paged", slots=4)
    # Any knob change is a different program → different namespace.
    assert fp != engine_fingerprint(SPEC.config, tp=2,
                                    kv_layout="paged", slots=4)
    assert fp != engine_fingerprint(SPEC.config, tp=1,
                                    kv_layout="dense", slots=4)
    other = get_model("lm-test-tiny")
    bigger = type(other.config)(**{**vars(other.config),
                                   "d_model": other.config.d_model * 2})
    assert fp != engine_fingerprint(bigger, tp=1, kv_layout="paged",
                                    slots=4)


def test_dispatch_keys_mirror_the_executable_set():
    keys = dispatch_keys(slots=4, prefill_len=32,
                         prefill_len_buckets=2, chunk_size=1,
                         speculative_k=0, prefill_chunk_tokens=0)
    # pow2 admit buckets from the floor (32 >> 2 = 8) up to the full
    # window, one decode executable, no verify/chunk shapes.
    assert keys == ["admit:s8", "admit:s16", "admit:s32", "decode:c1"]
    spec_keys = dispatch_keys(slots=4, prefill_len=32,
                              prefill_len_buckets=0, chunk_size=4,
                              speculative_k=3, prefill_chunk_tokens=16)
    assert spec_keys == ["admit:s32", "decode:c4", "verify:k3",
                         "chunk:w16"]


def test_manifest_merge_and_torn_manifest_reads_empty(tmp_path):
    cache = CompileCache(str(tmp_path))
    fp = "f" * 32
    assert cache.load(fp) == set()
    cache.record(fp, ["admit:s8", "decode:c1"])
    # A second newborn racing on the shared volume MERGES its keys.
    other = CompileCache(str(tmp_path))
    other.record(fp, ["admit:s16"])
    assert cache.load(fp) == {"admit:s8", "admit:s16", "decode:c1"}
    # Torn / garbage / wrong-version manifests read as empty — a birth
    # then compiles; it must never crash.
    (tmp_path / f"manifest-{fp}.json").write_text("{torn")
    assert cache.load(fp) == set()
    (tmp_path / f"manifest-{fp}.json").write_text(
        json.dumps({"version": 999, "keys": ["admit:s8"]}))
    assert cache.load(fp) == set()


def test_account_splits_hits_from_misses(tmp_path):
    fp = "a" * 32
    first = CompileCache(str(tmp_path))
    keys = ["admit:s8", "admit:s16", "decode:c1"]
    assert first.account(fp, keys) == (0, 3)  # cold node: all compiled
    second = CompileCache(str(tmp_path))
    assert second.account(fp, keys) == (3, 0)  # warm node: all reused
    assert second.account(fp, keys + ["verify:k3"]) == (3, 1)
    assert (second.hits, second.misses) == (6, 1)
    # A different fingerprint shares nothing.
    assert CompileCache(str(tmp_path)).account("b" * 32, keys) == (0, 3)


# ---------------------------------------------------------------------------
# /healthz warming (raw socket) + gateway UpstreamHealth
# ---------------------------------------------------------------------------


def _raw_get(port: int, path: str) -> tuple[int, dict]:
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall((f"GET {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                   "Connection: close\r\n\r\n").encode())
        data = b""
        while True:
            chunk = s.recv(4096)
            if not chunk:
                break
            data += chunk
    head, _, body = data.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    return status, json.loads(body or b"{}")


def test_healthz_reports_warming_until_warm_and_gateway_excludes():
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=16,
                     max_new_tokens=4),
        port=0, grpc_port=None, batch_timeout_ms=2)
    gate = threading.Event()
    orig_warmup = server.engine.warmup

    def gated_warmup():
        gate.wait(60)
        orig_warmup()

    server.engine.warmup = gated_warmup
    boot = threading.Thread(target=server.start, daemon=True)
    boot.start()
    try:
        deadline = time.monotonic() + 30
        while server.port == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.port != 0, "HTTP port never bound"

        # Raw-socket probe — the exact bytes a gateway health prober
        # sends: alive (200, connection accepted) but warming.
        status, body = _raw_get(server.port, "/healthz")
        assert (status, body["status"]) == (200, "warming")
        status, body = _raw_get(server.port, "/readyz")
        assert status == 503 and body["ready"] is False

        # The gateway's view: route-excluded, but NOT a failure — no
        # ejection machinery arms, so warm-up exit costs no half-open
        # trial.
        health = UpstreamHealth()
        health.probe(["svc"], lambda s: f"127.0.0.1:{server.port}")
        assert not health.admits("svc")
        # Fail-open: an all-warming pool still beats serving nobody.
        assert health.filter_healthy(["svc"]) == ["svc"]
        health.set_warming("other", False)
        assert health.filter_healthy(["svc", "other"]) == ["other"]
        cell = health._state["svc"]
        assert cell["consecutive_failures"] == 0
        assert cell["ejections"] == 0

        gate.set()
        boot.join(timeout=60)
        assert not boot.is_alive(), "warm path never completed"
        status, body = _raw_get(server.port, "/healthz")
        assert (status, body["status"]) == (200, "ok")
        # The next probe readmits instantly — no penalty to pay down.
        health.probe(["svc"], lambda s: f"127.0.0.1:{server.port}")
        assert health.admits("svc")
        assert health._state["svc"]["ejections"] == 0
    finally:
        gate.set()
        server.stop()


# ---------------------------------------------------------------------------
# Donor fallback chain (death mid-pull) and checkpoint birth
# ---------------------------------------------------------------------------


class _HalfDeadDonor:
    """Serves chunk seq 0 of a REAL multi-chunk envelope plan, then
    drops the connection — a donor dying mid-pull. The newborn must
    move to the next donor with nothing partial installed."""

    def __init__(self, params, version: int):
        envs = weights_mod.pack_weights(params, version,
                                        chunk_bytes=1024)
        assert len(envs) >= 2, "need a multi-chunk plan to die mid-pull"
        self.requests = 0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                outer.requests += 1
                body = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                seq = json.loads(body or b"{}").get("seq", 0)
                if seq == 0:
                    payload = json.dumps(envs[0]).encode()
                    self.send_response(200)
                    self.send_header("Content-Length",
                                     str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                else:  # die mid-pull: abrupt close, no response
                    self.connection.close()

            def log_message(self, *a):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()


def _write_checkpoint(path: str) -> object:
    """Seed a real checkpoint; returns the params it will restore."""
    from kubeflow_tpu.train import checkpoint as ckpt_lib
    from kubeflow_tpu.train.optimizers import OptimizerConfig
    from kubeflow_tpu.train.trainer import init_state

    state = init_state(jax.random.PRNGKey(0), SPEC, OptimizerConfig())
    ckpt_lib.save(path, 1, state)
    return state.params


def test_donor_death_mid_pull_falls_back_without_partial_install(
        tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt_params = _write_checkpoint(ckpt_dir)
    donor = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=16,
                     max_new_tokens=4, kv_layout="paged",
                     kv_block_size=4),
        port=0, grpc_port=None, batch_timeout_ms=2)
    donor.start()
    half_dead = None
    try:
        # Distinct epoch on the donor: prove the newborn's bytes came
        # from the PEER, not the checkpoint (or a fresh init).
        weights_mod.push_weights(f"127.0.0.1:{donor.port}",
                                 "lm-test-tiny", P_DONOR, 3,
                                 chunk_bytes=1024)
        half_dead = _HalfDeadDonor(P_DONOR, 3)
        peers = (f"127.0.0.1:1,"               # dead: connect refused
                 f"127.0.0.1:{half_dead.port},"  # dies mid-pull
                 f"127.0.0.1:{donor.port}")      # live donor
        newborn = InferenceEngine(EngineConfig(
            model="lm-test-tiny", batch_size=2, max_seq_len=16,
            max_new_tokens=4, weight_peers=peers,
            weight_pull_timeout_s=30.0, checkpoint_dir=ckpt_dir))
        # The mid-pull death was real: chunk 0 served, chunk 1 dropped.
        assert half_dead.requests >= 2
        # Complete-or-nothing: the install is the live donor's exact
        # bytes at the donor's epoch — no leaf from the torn pull, no
        # checkpoint fallback, no partial epoch.
        assert newborn.weight_pull_source == "peer"
        assert newborn.boot_weights_version == 3
        assert _trees_equal(newborn.params, _held(P_DONOR))
        assert not _trees_equal(newborn.params, _held(ckpt_params))
    finally:
        if half_dead is not None:
            half_dead.stop()
        donor.stop()


def test_every_donor_dead_falls_back_to_checkpoint_byte_identical(
        tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt_params = _write_checkpoint(ckpt_dir)
    newborn = InferenceEngine(EngineConfig(
        model="lm-test-tiny", batch_size=2, max_seq_len=16,
        max_new_tokens=4, weight_peers="127.0.0.1:1,127.0.0.1:2",
        weight_pull_timeout_s=5.0, checkpoint_dir=ckpt_dir))
    assert newborn.weight_pull_source == "checkpoint"
    assert newborn.boot_weights_version == 0
    assert _trees_equal(newborn.params, _held(ckpt_params))


# ---------------------------------------------------------------------------
# Fleet ramped admission
# ---------------------------------------------------------------------------


class _StubReplica:
    def __init__(self, depth: int = 0):
        self._active_count = depth
        self.submitted: list = []

    def submit(self, tokens, want, temperature=0.0, *, request_id=None):
        self.submitted.append(list(tokens))
        return object()

    def metrics(self):
        return {"prefix_hits": 0, "prefix_misses": len(self.submitted)}

    def stop(self):
        pass


PROMPTS = [[g, g + 1, g + 2, 7] for g in range(60)]


def test_warming_newborn_takes_no_affine_share_until_marked_warm():
    reps = {f"r{i}": _StubReplica() for i in range(2)}
    fleet = DecoderFleet(dict(reps), affinity_tokens=4)
    before = {tuple(p): fleet.route(p) for p in PROMPTS}

    fleet.add_replica("rN", _StubReplica(), warming=True)
    assert fleet.metrics()["warming"] == ["rN"]
    assert fleet.metrics()["replicas_added"] == 1
    # No affine share while warming — every established key stays put.
    for p in PROMPTS:
        assert fleet.route(p) == before[tuple(p)]

    fleet.mark_warm("rN")
    assert fleet.metrics()["warming"] == []
    after = {tuple(p): fleet.route(p) for p in PROMPTS}
    moved = [k for k, v in after.items() if v != before[k]]
    # Rendezvous rebalance: the newborn takes ~1/N of keys, and every
    # key that moved moved ONTO the newborn (nobody else's keys churn).
    assert moved
    assert all(after[k] == "rN" for k in moved)


def test_warming_newborn_is_in_the_spill_pool():
    reps = {f"r{i}": _StubReplica(depth=3) for i in range(2)}
    fleet = DecoderFleet(dict(reps), affinity_tokens=4, pressure=2)
    fleet.add_replica("rN", _StubReplica(depth=0), warming=True)
    # Every established replica is over pressure; the warming newborn
    # is the least-loaded spill target — ramped traffic, immediately.
    assert {fleet.route(p) for p in PROMPTS} == {"rN"}


def test_duplicate_add_replica_rejected_and_donor_for_skips_warming():
    fleet = DecoderFleet({"r0": _StubReplica()}, affinity_tokens=4)
    fleet.add_replica("r1", _StubReplica(), warming=True)
    with pytest.raises(ValueError):
        fleet.add_replica("r1", _StubReplica())
    # The only other member is warming: not a viable donor.
    assert fleet.donor_for("r0") is None
    assert fleet.donor_for("r1") == "r0"
    fleet.mark_warm("r1")
    assert fleet.donor_for("r0") == "r1"
