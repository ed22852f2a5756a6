#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # every phase; exit 0 and a JSON last line
    python chip_smoke.py --phases kernels,train   # a subset, while debugging

Drives the two hot paths once, through the entry points a user calls, at
the full width and depth of a registered preset (``llama-1b``, random
weights from a seed), on every chip the machine has:

- **kernels**: every Pallas kernel the repo ships, compiled
  (``interpret=False``) at llama-1b shapes and compared with its XLA twin;
  and the lowered train step must contain a Mosaic custom call.
- **train**: ``python -m kubeflow_tpu.train.loop`` for a few steps at
  seq 512 from a KTPU token file made here from a seed (so the native
  loader is built from ``native/tokenstore.cc``): loss finite and
  falling, a checkpoint saved.
- **serve**: ``python -m kubeflow_tpu.serving --model-name llama-1b
  --max-seq-len 512`` answering, over REST, a plain predict, concurrent
  generations of different lengths, a repeated greedy prompt and a
  streamed generation, with the ``/monitoring`` counters checked.
- **cache**: the same two commands a second time, each in a fresh
  process with a ``jax.monitoring`` listener: the train step (resuming
  from the checkpoint the train phase saved) and the decode dispatch set
  must report persistent-cache hits.
- **multichip** (>= 4 chips; otherwise printed as skipped): ``python -m
  kubeflow_tpu.train.loop`` with ``{"mesh": {"fsdp": 2, "tensor": 2}}``
  against a one-chip run of the same seed and batch, parameters really
  spread over four chips, and the server with ``--tp-shards 4
  --kv-layout paged``.

A chip belongs to one process at a time, so this parent never imports
JAX: each phase is a child process that owns the chip(s) for its lifetime
and has exited before the next starts. Any phase that fails, times out
or finds no TPU ends the run non-zero with the child's own output; there
is no substitute model and no CPU fallback. Seconds and tokens/s printed
here are set-up facts with the device named, not benchmark results.

The last line of a complete, successful run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import resource
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
# Scratch for this run (token file, checkpoint, dispatch manifests):
# fixed and git-ignored, like the compile cache beside it.
WORK = os.path.join(REPO, ".chip_smoke")
PHASES = ("kernels", "train", "serve", "cache", "multichip")

MODEL = "llama-1b"
VOCAB = 32_000
SEQ_LEN = 512
MAX_NEW = 32
TRAIN_STEPS = 10
RESUME_STEPS = 2
# The loss must fall by more than this over TRAIN_STEPS. On the v5e the
# seeded run goes 10.83 -> 7.46 (lr 3e-4; 1e-3 is noisy, 1e-2 diverges).
MIN_LOSS_DROP = 1.0
# The four-chip run: this mesh against one chip, first-step losses within
# this relative distance (bf16 matmuls reduce in a different order).
MULTICHIP_MESH = {"fsdp": 2, "tensor": 2}
MESH_LOSS_RTOL = 2e-2
ONE_CHIP_LOSS = "one-chip first-step loss:"
# Per child. A passing one-chip run takes about 350 s of the 1200 s the
# contract allows; the cache phase reuses the train and serve limits.
TIMEOUT_S = {"kernels": 300, "train": 360, "serve": 420, "multichip": 600}
# The machine this runs on for the record caps file size (RLIMIT_FSIZE,
# value not known: orbax's default 2 GiB data files died there with
# EFBIG, having written all but 294 MB). Every child runs under this cap,
# so a file that would not survive there fails here first. The largest
# the run writes are checkpoint data files (train/checkpoint.py keeps
# them under 32 MiB) and compile-cache entries (the train step's
# executable is 32 MB before compression).
FILE_LIMIT_BYTES = 64 << 20

_DEVICE_RE = re.compile(
    r"device: jax=(\S+) platform=(\S+) device_kind='([^']*)' count=(\d+)")
_children: list[subprocess.Popen] = []


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Parent: child processes
# ---------------------------------------------------------------------------


def _spawn(phase: str, cmd: list[str], **env_extra: str
           ) -> tuple[subprocess.Popen, list[str]]:
    """Start ``cmd`` in its own process group; its output is echoed under
    a ``[phase]`` prefix and collected."""
    say(f"[{phase}] $ {' '.join(cmd)}")
    env = dict(os.environ, PYTHONUNBUFFERED="1", PYTHONPATH=REPO + (
        os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else ""), **env_extra)
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, errors="replace",
        start_new_session=True)
    _children.append(proc)
    lines: list[str] = []

    def pump():
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            say(f"[{phase}] {line}")

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    proc._reader = reader  # joined by _finish
    return proc, lines


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def _finish(phase: str, proc: subprocess.Popen, timeout: float) -> int:
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _kill(proc)
        raise PhaseFailed(f"{phase}: timed out after {timeout:.0f}s") \
            from None
    proc._reader.join(timeout=10)
    return rc


def run_child(phase: str, cmd: list[str], timeout: float,
              **env_extra: str) -> list[str]:
    """Run one child to completion; non-zero exit fails the phase (its
    output has already been echoed)."""
    proc, lines = _spawn(phase, cmd, **env_extra)
    rc = _finish(phase, proc, timeout)
    if rc != 0:
        tail = "\n".join(lines[-15:])
        raise PhaseFailed(f"{phase}: child exited {rc}:\n{tail}")
    check_cache_io(phase, lines)
    return lines


def check_cache_io(phase: str, lines: list[str]) -> None:
    """JAX only warns when it cannot write or read a compile-cache entry
    (over the file-size limit it leaves a truncated one); here that
    fails the phase."""
    for line in lines:
        if re.search(r"Error (writing|reading) persistent compilation "
                     r"cache entry", line):
            raise PhaseFailed(f"{phase}: {line.strip()}")


def device_of(phase: str, lines: list[str]) -> dict:
    """The device a child reported (every entry point prints it); a
    child that ran anywhere but a TPU fails the phase."""
    for line in lines:
        m = _DEVICE_RE.search(line)
        if m:
            dev = {"jax": m.group(1), "platform": m.group(2),
                   "kind": m.group(3), "count": int(m.group(4))}
            if dev["platform"] != "tpu":
                raise PhaseFailed(
                    f"{phase}: no TPU — the child ran on platform="
                    f"{dev['platform']!r} ({dev['kind']!r})")
            return dev
    raise PhaseFailed(f"{phase}: the child never named its device")


def report_of(phase: str, lines: list[str]) -> dict:
    """The ``CHILD_REPORT`` a traced child prints on exit."""
    for line in reversed(lines):
        if line.startswith("CHILD_REPORT "):
            return json.loads(line[len("CHILD_REPORT "):])
    raise PhaseFailed(f"{phase}: the traced child printed no report")


def traced(module: str, *args: str) -> list[str]:
    """``python -m module args`` in a child that also counts
    persistent-cache hits (see :func:`child_traced`)."""
    return [sys.executable, os.path.abspath(__file__), "--child", "traced",
            module, *args]


# ---------------------------------------------------------------------------
# Parent: train phase
# ---------------------------------------------------------------------------


CORPUS = os.path.join(WORK, "corpus.ktpu")


def write_corpus() -> None:
    """Make the token file in a child held to the CPU: the writer lives
    in a package that imports JAX, which this parent does not."""
    run_child("corpus", [sys.executable, os.path.abspath(__file__),
                         "--child", "corpus"], 120, JAX_PLATFORMS="cpu")


def child_corpus(seed: int = 0, n_tokens: int = 1 << 20) -> int:
    """A seeded KTPU token file the model can learn something from in a
    handful of steps: a Zipf draw over 256 of the vocabulary's ids."""
    import numpy as np

    from kubeflow_tpu.train.tokenstore import write_token_file

    rng = np.random.default_rng(seed)
    ids = rng.choice(VOCAB, size=256, replace=False)
    p = 1.0 / np.arange(1, 257)
    write_token_file(CORPUS,
                     ids[rng.choice(256, size=n_tokens, p=p / p.sum())])
    print(f"wrote {n_tokens} tokens (seed {seed}) to {CORPUS}")
    return 0


def train_config(steps: int, **extra) -> str:
    cfg = {
        "model": MODEL, "batch_size": 8, "seq_len": SEQ_LEN, "steps": steps,
        "log_every": 1, "seed": 0,
        "data_path": CORPUS,
        "checkpoint_dir": os.path.join(WORK, "ckpt"),
        "checkpoint_every": 10 ** 9,  # only the final save
        "optimizer": {"name": "adafactor", "learning_rate": 3e-4,
                      "warmup_steps": 2, "total_steps": 100},
    }
    cfg.update(extra)
    return json.dumps(cfg)


def losses_of(lines: list[str]) -> dict[int, float]:
    out = {}
    for line in lines:
        m = re.match(r"step=(\d+) loss=(\S+)", line)
        if m:
            out[int(m.group(1))] = float(m.group(2))
    return out


def check_losses(phase: str, losses: dict[int, float], steps) -> None:
    import math

    missing = [s for s in steps if s not in losses]
    if missing:
        raise PhaseFailed(f"{phase}: no loss logged for steps {missing}")
    bad = {s: v for s, v in losses.items() if not math.isfinite(v)}
    if bad:
        raise PhaseFailed(f"{phase}: non-finite loss {bad}")


def phase_train() -> dict:
    shutil.rmtree(os.path.join(WORK, "ckpt"), ignore_errors=True)
    write_corpus()
    t0 = time.perf_counter()
    lines = run_child(
        "train", [sys.executable, "-m", "kubeflow_tpu.train.loop",
                  train_config(TRAIN_STEPS)], TIMEOUT_S["train"])
    secs = time.perf_counter() - t0
    dev = device_of("train", lines)
    losses = losses_of(lines)
    check_losses("train", losses, range(1, TRAIN_STEPS + 1))
    first, last = losses[1], losses[TRAIN_STEPS]
    if not last < first - MIN_LOSS_DROP:
        raise PhaseFailed(
            f"train: loss did not fall: step 1 {first:.4f} -> step "
            f"{TRAIN_STEPS} {last:.4f} (want a drop of more than "
            f"{MIN_LOSS_DROP})")
    backend = [ln for ln in lines if ln.startswith("token store: backend=")]
    if not backend:
        raise PhaseFailed("train: the loop never named its token-store "
                          "backend")
    result = json.loads(lines[-1])
    if result["step"] != TRAIN_STEPS or result["devices"] != dev["count"]:
        raise PhaseFailed(
            f"train: ran {result['step']} steps on {result['devices']} "
            f"device(s); wanted {TRAIN_STEPS} on all {dev['count']}")
    if not os.path.isdir(os.path.join(WORK, "ckpt", str(TRAIN_STEPS))):
        raise PhaseFailed(f"train: no checkpoint at step {TRAIN_STEPS}")
    say(f"train: ok — {MODEL} seq{SEQ_LEN} bs8 adafactor, loss "
        f"{first:.4f} -> {last:.4f} over {TRAIN_STEPS} steps on "
        f"{dev['count']} x {dev['kind']}; {backend[0]}; checkpoint saved; "
        f"{secs:.0f}s wall incl. compile (set-up fact)")
    return dev


# ---------------------------------------------------------------------------
# Parent: serve phase
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, path: str, body: dict | None = None,
          timeout: float = 300.0):
    """(status, parsed JSON or text)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode(errors="replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def _counters(port: int) -> dict[str, float]:
    status, text = _http(port, "/monitoring/prometheus/metrics")
    if status != 200:
        raise PhaseFailed(f"/monitoring answered {status}")
    out = {}
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2 and not line.startswith("#"):
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                pass
    return out


def _prompt(n: int, salt: int) -> list[int]:
    return [(salt * 7919 + i * 104729) % VOCAB for i in range(n)]


class Server:
    """One ``python -m kubeflow_tpu.serving`` child and its REST port."""

    def __init__(self, phase: str, extra_args: list[str], *,
                 trace: bool = False):
        self.phase = phase
        self.port = _free_port()
        args = ["--model-name", MODEL, "--max-seq-len", str(SEQ_LEN),
                "--max-new-tokens", str(MAX_NEW),
                "--rest-port", str(self.port),
                "--grpc-port", str(_free_port()),
                # Not an engine option: where the dispatch manifest goes.
                # Naming it makes the boot warm (and so compile) the
                # whole dispatch set before /healthz says ok.
                "--compile-cache-dir", os.path.join(WORK, "manifests"),
                *extra_args]
        cmd = (traced("kubeflow_tpu.serving", *args) if trace else
               [sys.executable, "-m", "kubeflow_tpu.serving", *args])
        self.t0 = time.perf_counter()
        self.proc, self.lines = _spawn(phase, cmd)

    def wait_ready(self, timeout: float) -> float:
        """Seconds until /healthz left ``warming`` for ``ok``."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                tail = "\n".join(self.lines[-15:])
                raise PhaseFailed(
                    f"{self.phase}: the server exited "
                    f"{self.proc.returncode} before it was ready:\n{tail}")
            try:
                status, body = _http(self.port, "/healthz", timeout=5)
            except (OSError, urllib.error.URLError):
                status, body = None, None  # port not bound yet
            if status == 200 and body.get("status") == "ok":
                return time.perf_counter() - self.t0
            if status == 500:
                raise PhaseFailed(
                    f"{self.phase}: /healthz reports a failed warm: {body}")
            time.sleep(1.0)
        _kill(self.proc)
        raise PhaseFailed(f"{self.phase}: still warming after {timeout:.0f}s")

    def generate(self, tokens: list[int], want: int, **extra) -> dict:
        status, body = _http(self.port, f"/v1/models/{MODEL}:predict", {
            "instances": [{"tokens": tokens, "max_new_tokens": want,
                           **extra}]})
        if status != 200:
            raise PhaseFailed(f"{self.phase}: generate answered {status}: "
                              f"{body}")
        pred = body["predictions"][0]
        toks = pred["tokens"]
        if len(toks) != want:
            raise PhaseFailed(f"{self.phase}: asked {want} tokens, got "
                              f"{len(toks)}")
        if not all(isinstance(t, int) and 0 <= t < VOCAB for t in toks):
            raise PhaseFailed(f"{self.phase}: token ids outside the "
                              f"vocabulary: {toks}")
        return pred

    def stop(self) -> list[str]:
        """SIGTERM; the server must drain and exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        rc = _finish(self.phase, self.proc, 60)
        if rc != 0:
            raise PhaseFailed(f"{self.phase}: the server exited {rc} on "
                              "SIGTERM (want a clean 0)")
        check_cache_io(self.phase, self.lines)
        return self.lines


def exercise_server(srv: Server) -> dict:
    """The request mix every serving phase sends; returns facts to print."""
    import math
    from concurrent.futures import ThreadPoolExecutor

    phase = srv.phase
    before = _counters(srv.port)
    if before.get("serving_warm_failed_shapes", 0) != 0:
        raise PhaseFailed(f"{phase}: serving_warm_failed_shapes="
                          f"{before['serving_warm_failed_shapes']}")

    # 1. plain predict: last-position logits through the predict path
    # (at seq 512 the forward takes the kernel training takes).
    status, body = _http(srv.port, f"/v1/models/{MODEL}:predict",
                         {"instances": [{"tokens": _prompt(20, 1)}]})
    if status != 200:
        raise PhaseFailed(f"{phase}: predict answered {status}: {body}")
    pred = body["predictions"][0]
    logits = pred["logits"]
    if len(logits) != VOCAB or not all(math.isfinite(x) for x in logits):
        raise PhaseFailed(f"{phase}: predict logits are not {VOCAB} "
                          "finite values")
    if pred["next_token"] != max(range(VOCAB), key=logits.__getitem__):
        raise PhaseFailed(f"{phase}: next_token is not argmax(logits)")

    # 2. concurrent generations, different prompt and output lengths.
    mix = [(5, 4), (37, 9), (SEQ_LEN * 25 // 64, 17),
           (SEQ_LEN - 12, MAX_NEW)]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(mix)) as pool:
        futures = [pool.submit(srv.generate, _prompt(n, 2 + i), want)
                   for i, (n, want) in enumerate(mix)]
    mix_s = time.perf_counter() - t0
    generated = sum(want for _, want in mix)

    for f in futures:
        f.result()  # each checked its own status, count and vocabulary
    # 3. the same greedy prompt twice, each time alone (the same
    # executables; a different admission batch may round differently,
    # and random weights make near-ties): the same tokens.
    once = srv.generate(_prompt(37, 3), 9)
    again = srv.generate(_prompt(37, 3), 9)
    if again["tokens"] != once["tokens"]:
        raise PhaseFailed(
            f"{phase}: greedy tokens differ for the same prompt: "
            f"{once['tokens']} vs {again['tokens']}")
    generated += 18

    # 4. one streamed generation, token by token.
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/models/{MODEL}:predict",
        data=json.dumps({"stream": True, "instances": [
            {"tokens": _prompt(37, 3), "max_new_tokens": 9}]}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        if resp.status != 200:
            raise PhaseFailed(f"{phase}: stream answered {resp.status}")
        records = [json.loads(ln) for ln in resp.read().splitlines() if ln]
    streamed = [r["token"] for r in records if "token" in r]
    done = records[-1]
    if (not done.get("done") or "error" in done or len(streamed) != 9
            or streamed != done["tokens"] or streamed != again["tokens"]):
        raise PhaseFailed(f"{phase}: bad stream: {records}")
    generated += 9

    # 5. the server's own counters moved by exactly this traffic.
    after = _counters(srv.port)
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "serving_requests_admitted_total", "serving_tokens_emitted_total",
        "serving_decode_dispatches_total",
        "serving_prefill_dispatches_total", "serving_errors_total")}
    if (moved["serving_requests_admitted_total"] != len(mix) + 3
            or moved["serving_tokens_emitted_total"] != generated
            or moved["serving_decode_dispatches_total"] <= 0
            or moved["serving_prefill_dispatches_total"] <= 0
            or moved["serving_errors_total"] != 0):
        raise PhaseFailed(f"{phase}: /monitoring counters did not move "
                          f"with the traffic: {moved}")
    return {"tokens": again["tokens"],
            "mix_tokens_per_s": round(sum(w for _, w in mix) / mix_s, 1)}


def phase_serve(extra_args=(), phase: str = "serve"
                ) -> tuple[dict, list[int]]:
    """Returns the device and the tokens generated for the probe prompt."""
    srv = Server(phase, list(extra_args))
    try:
        ready_s = srv.wait_ready(TIMEOUT_S["serve"])
        facts = exercise_server(srv)
        lines = srv.stop()
    finally:
        _kill(srv.proc)
    dev = device_of(phase, lines)
    say(f"{phase}: ok — {MODEL} max_seq_len {SEQ_LEN} "
        f"{''.join(a + ' ' for a in extra_args)}on {dev['count']} x "
        f"{dev['kind']}: predict, "
        f"4 concurrent generations, repeated greedy prompt and stream all "
        f"answered; counters moved; clean exit; ready after {ready_s:.0f}s "
        f"incl. compile, {facts['mix_tokens_per_s']} tokens/s over the "
        f"4-request mix (set-up facts)")
    return dev, facts["tokens"]


# ---------------------------------------------------------------------------
# Parent: cache phase
# ---------------------------------------------------------------------------


def phase_cache(serve_tokens: list[int] | None) -> dict:
    """The two compiles again, each in a fresh traced process.
    ``serve_tokens``: what the serve phase's server generated for the
    probe prompt, when that phase ran."""
    # Train step: resume from the train phase's checkpoint (which also
    # proves the restore) and take two more steps.
    total = TRAIN_STEPS + RESUME_STEPS
    lines = run_child("cache", traced("kubeflow_tpu.train.loop",
                                      train_config(total)),
                      TIMEOUT_S["train"])
    dev = device_of("cache", lines)
    if not any(f"resumed from checkpoint step {TRAIN_STEPS}" in ln
               for ln in lines):
        raise PhaseFailed("cache: the loop did not restore the checkpoint "
                          f"saved at step {TRAIN_STEPS}")
    losses = losses_of(lines)
    check_losses("cache", losses, range(TRAIN_STEPS + 1, total + 1))
    import math

    if not losses[total] < math.log(VOCAB):
        # An untrained model cannot beat the uniform guess: the restored
        # state must be the trained one.
        raise PhaseFailed(
            f"cache: loss after resume is {losses[total]:.4f}, not under "
            f"ln({VOCAB}) = {math.log(VOCAB):.2f}: the trained state was "
            "not restored")
    train_rep = report_of("cache", lines)

    srv = Server("cache", [], trace=True)
    try:
        ready_s = srv.wait_ready(TIMEOUT_S["serve"])
        again = srv.generate(_prompt(37, 3), 9)
        lines = srv.stop()
    finally:
        _kill(srv.proc)
    device_of("cache", lines)
    if serve_tokens is not None and again["tokens"] != serve_tokens:
        raise PhaseFailed(
            "cache: the second server's greedy tokens differ from the "
            f"first's: {serve_tokens} vs {again['tokens']}")
    serve_rep = report_of("cache", lines)

    for name, rep in (("train step", train_rep),
                      ("decode dispatch set", serve_rep)):
        if rep["cache_hits"] <= 0:
            raise PhaseFailed(
                f"cache: the second compile of the {name} reported no "
                f"persistent-cache hit: {rep}")
    say(f"cache: ok — second compile in a fresh process, cache at "
        f"{train_rep['cache_dir']}: train step {train_rep['cache_hits']} "
        f"hit(s) / {train_rep['cache_misses']} miss(es), "
        f"{train_rep['backend_compile_s']:.1f}s compiling, resumed from "
        f"step {TRAIN_STEPS} and lost nothing (loss "
        f"{losses[total]:.4f}); server {serve_rep['cache_hits']} hit(s) / "
        f"{serve_rep['cache_misses']} miss(es), "
        f"{serve_rep['backend_compile_s']:.1f}s compiling, ready after "
        f"{ready_s:.0f}s (set-up facts)")
    return dev


# ---------------------------------------------------------------------------
# Children (these import JAX; the parent above never does)
# ---------------------------------------------------------------------------


def _child_device() -> dict:
    """Place the compile cache, print where it is and which device this
    child is on, and refuse anything but a TPU."""
    from kubeflow_tpu.utils.jaxenv import (
        device_line,
        place_compile_cache,
        require_tpu,
    )

    print(f"compile cache: {place_compile_cache()}")
    print(device_line(), flush=True)
    return require_tpu()


def child_traced(module: str, argv: list[str]) -> int:
    """``python -m module argv`` exactly — ``runpy`` runs the module as
    ``__main__`` — with a ``jax.monitoring`` listener installed first, so
    the run can say how many executables came out of the persistent
    cache. Prints one ``CHILD_REPORT {json}`` line on the way out."""
    import runpy

    import jax

    from kubeflow_tpu.utils.jaxenv import place_compile_cache

    counts = {"cache_hits": 0, "cache_misses": 0, "backend_compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["cache_misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["backend_compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    sys.argv = [module, *argv]
    code = 0
    try:
        runpy.run_module(module, run_name="__main__", alter_sys=True)
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (0 if e.code is None
                                                       else 1)
    finally:
        counts["cache_dir"] = place_compile_cache()
        counts["backend_compile_s"] = round(counts["backend_compile_s"], 2)
        print("CHILD_REPORT " + json.dumps(counts), flush=True)
    return code


def _rel_l2(a, b) -> tuple[float, float]:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return (float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)),
            float(np.max(np.abs(a - b))))


def child_kernels() -> int:
    """Each shipped Pallas kernel, compiled and run once at llama-1b
    shapes against its XLA twin. Every check runs (one call shows every
    refusal); any failure exits non-zero with the compiler's message."""
    import traceback

    import jax
    import jax.numpy as jnp
    import numpy as np

    _child_device()
    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.ops.attention import (
        flash_attention,
        paged_decode_attention,
    )
    from kubeflow_tpu.ops.norms import rms_norm

    cfg = get_model(MODEL).config
    hq, hkv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    failures = []

    def check(name, tol, got_fn, want_fn):
        """got_fn compiles+runs the kernel, want_fn its XLA twin; both
        return a pytree of arrays compared leaf by leaf in relative L2."""
        try:
            got = jax.block_until_ready(got_fn())
            # The twin is the reference: full-precision matmuls, so the
            # distance measured is the kernel's, not the twin's rounding.
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(want_fn())
            worst = max(_rel_l2(g, w) for g, w in zip(
                jax.tree.leaves(got), jax.tree.leaves(want)))
            finite = all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                         for g in jax.tree.leaves(got))
            ok = finite and worst[0] <= tol
            print(f"kernel {name}: compiled and ran; rel_l2={worst[0]:.2e} "
                  f"max_abs={worst[1]:.2e} tol={tol:.0e} finite={finite} "
                  f"{'PASS' if ok else 'FAIL'}", flush=True)
            if not ok:
                failures.append(name)
        except Exception:
            print(f"kernel {name}: REFUSED\n{traceback.format_exc()}",
                  flush=True)
            failures.append(name)

    # --- paged decode attention: the serving engine's default geometry
    # (8 slots, block size 16, 512 + 32 positions a row), bf16 and int8.
    slots, bs, total = 8, 16, SEQ_LEN + MAX_NEW
    mb = total // bs
    n_blocks = slots * mb
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    # Peaked scores (x4), so a wrong block or mask moves the output by
    # O(1) while rounding moves it by O(1e-2).
    q = (4 * jax.random.normal(kq, (slots, hq, hd))).astype(jnp.bfloat16)
    k_pool = jax.random.normal(kk, (n_blocks, bs, hkv, hd)).astype(
        jnp.bfloat16)
    v_pool = jax.random.normal(kv, (n_blocks, bs, hkv, hd)).astype(
        jnp.bfloat16)
    table = rng.permutation(n_blocks).reshape(slots, mb).astype(np.int32)
    pos = np.array([0, 15, 16, 100, 255, 300, 511, total - 1], np.int32)
    for row in range(slots):  # blocks past pos are unallocated sentinels
        table[row, pos[row] // bs + 1:] = n_blocks
    table, pos = jnp.asarray(table), jnp.asarray(pos)

    def quantize(pool):
        p32 = pool.astype(jnp.float32)
        scale = jnp.max(jnp.abs(p32), axis=-1) / 127.0
        return {"q": jnp.round(p32 / scale[..., None]).astype(jnp.int8),
                "scale": scale}

    # Arrays go in as arguments, never closed over: a closure is baked
    # into the executable as a constant, and the compile-cache entry with
    # it (these ran to 40-70 MB that way, past FILE_LIMIT_BYTES).
    for label, kp, vp in (("bf16", k_pool, v_pool),
                          ("int8", quantize(k_pool), quantize(v_pool))):
        def run(impl, kp=kp, vp=vp):
            return jax.jit(lambda q_, kp_, vp_, table_, pos_:
                           paged_decode_attention(
                               q_, kp_, vp_, table_, pos_, n_kv_heads=hkv,
                               implementation=impl))(q, kp, vp, table, pos)
        check(f"paged_decode_attention[{label} pool, block {bs}]", 3e-2,
              lambda run=run: run("pallas"), lambda run=run: run("xla"))

    # --- sparse decode attention at MiniCPM-SALA's widths (2 KV heads of
    # 128, groups of 16, blocks of 64): rows on both sides of dense_len,
    # one with nothing allocated, a shuffled pool; ONE selection, the
    # program's own, read by the kernel and by the XLA gather.
    from kubeflow_tpu.ops import sparse_attention as sa

    spec = sa.SparseSpec(kernel=32, stride=16, block=64, topk=64,
                         init_blocks=1, window=2048, dense_len=8192)
    s_pos = np.array([0, 63, 64, 4095, 8191, 8192, 12000, 200], np.int32)
    s_mb, s_pool = 192, 1024
    order = rng.permutation(s_pool).tolist()
    s_table = np.full((len(s_pos), s_mb), s_pool, np.int32)
    for row, p in enumerate(s_pos[:-1]):  # the last row: sentinels only
        s_table[row, :p // 64 + 1] = [order.pop() for _ in range(p // 64 + 1)]
    sq = (4 * jax.random.normal(kq, (len(s_pos), 2, 16, 128))).astype(
        jnp.bfloat16)
    spk, spv = (jax.random.normal(k_, (2, s_pool, 2, 64, 128)).astype(
        jnp.bfloat16) for k_ in (kk, kv))

    s_table, s_pos = jnp.asarray(s_table), jnp.asarray(s_pos)

    def select(q_, pk_, table_, pos_):
        rows = pk_[1][jnp.minimum(table_, s_pool - 1)].transpose(
            0, 2, 1, 3, 4).reshape(len(s_pos), 2, s_mb * 64, 128)
        idx, ok = sa.select_blocks(
            q_[:, :, :, None], sa.compress_keys(rows, spec), pos_[:, None],
            s_mb, spec)
        return idx[:, :, 0], ok[:, :, 0]
    s_idx, s_ok = jax.jit(select)(sq, spk, s_table, s_pos)

    def sparse(impl):
        return jax.jit(
            lambda q_, pk_, pv_, table_, idx_, ok_, pos_:
            sa.sparse_decode_attention(q_, pk_, pv_, 1, table_, idx_, ok_,
                                       pos_, spec, implementation=impl))(
            sq, spk, spv, s_table, s_idx, s_ok, s_pos)
    check("sparse_decode_attention[bf16 pool, block 64]", 3e-2,
          lambda: sparse("pallas"), lambda: sparse("xla"))

    # --- dense decode attention at Ouro-2.6B's widths (16 query heads on 16
    # KV heads) and Mistral's (32 on 8): a 3-layer store read at its middle
    # layer, rows that hold nothing, one position, a chunk's edge and one
    # past it, and all of a row of 1,280 (ten chunks of 128).
    from kubeflow_tpu.ops.attention import dense_decode_attention

    d_len = jnp.asarray([0, 1, 64, 128, 129, 840, 1280, 0], jnp.int32)
    for d_hq, d_hkv in ((16, 16), (32, 8)):
        dq = (4 * jax.random.normal(kq, (len(d_len), d_hq, 128))).astype(
            jnp.bfloat16)
        dk, dv = (jax.random.normal(k_, (3, len(d_len), 1280, d_hkv, 128)
                                    ).astype(jnp.bfloat16) for k_ in (kk, kv))

        def dense(impl):  # called by ``check`` inside this iteration
            return jax.jit(
                lambda q_, k_, v_, li, n: dense_decode_attention(
                    q_, k_, v_, li, n, n_kv_heads=d_hkv,
                    implementation=impl))(dq, dk, dv, jnp.int32(1), d_len)
        check(f"dense_decode_attention[bf16 store, {d_hq} heads on "
              f"{d_hkv}]", 3e-2, lambda: dense("pallas"),
              lambda: dense("xla"))

    # --- its latent form at Kanana-2-30B-A3B's widths (its own kernel,
    # latent_decode_attention): a row of 640 (a latent of 512, a rotary
    # key of 64, 64 zeros) read whole as the key of 32 heads and by its
    # first 512 as their value, at the model's scale; rows that hold
    # nothing, one position, either side of 256 (two copies of 128), 2,048,
    # 3,500 and all 8,192.
    l_len = jnp.asarray([0, 1, 255, 256, 257, 2048, 3500, 8192], jnp.int32)
    l_col = (jnp.arange(640) < 576).astype(jnp.bfloat16)
    lq = (4 * jax.random.normal(kq, (len(l_len), 32, 640))).astype(
        jnp.bfloat16) * l_col
    l_store = jax.random.normal(kk, (3, len(l_len), 8192, 640)).astype(
        jnp.bfloat16) * l_col

    def latent(impl):
        return jax.jit(
            lambda q_, s_, li, n: dense_decode_attention(
                q_, s_, None, li, n, n_kv_heads=1, v_dim=512,
                sm_scale=192 ** -0.5, implementation=impl))(
            lq, l_store, jnp.int32(1), l_len)
    check("latent_decode_attention[bf16 store, 32 heads over a row of 640]",
          3e-2, lambda: latent("pallas"), lambda: latent("xla"))

    # --- the grouped expert MLP at Kanana-2-30B-A3B's widths: a decode
    # step's 64 rows × 6 of 128 experts of 2048 × 768, sorted by expert,
    # through layer 1 of two-layer stacks, against every expert applied to
    # every pair and each pair's own kept (XLA's ragged-dot kernel, the
    # program's other path, does not compile at "highest" precision).
    from kubeflow_tpu.ops.grouped_experts import grouped_expert_mlp

    e_n, e_d, e_f = 128, 2048, 768
    gw, uw = (jax.random.normal(k_, (2, e_n, e_d, e_f)).astype(jnp.bfloat16)
              * e_d ** -0.5 for k_ in (kk, kv))
    dw = jax.random.normal(kq, (2, e_n, e_f, e_d)).astype(
        jnp.bfloat16) * e_f ** -0.5
    chosen = jnp.asarray(np.stack([rng.choice(e_n, 6, replace=False)
                                   for _ in range(64)]).reshape(-1))
    e_sizes = jnp.bincount(chosen, length=e_n).astype(jnp.int32)
    e_xs = jax.random.normal(key, (64, e_d)).astype(jnp.bfloat16)[
        jnp.argsort(chosen) // 6]

    def every_expert(xs, own_expert, g_, u_, d_):
        def own(spec, h, w):
            every = jnp.einsum(spec, h, w[1].astype(jnp.float32))
            return jnp.take_along_axis(every, own_expert[:, None, None],
                                       axis=1)[:, 0]
        x = xs.astype(jnp.float32)
        h = jax.nn.silu(own("pd,edf->pef", x, g_)) * own("pd,edf->pef", x,
                                                          u_)
        return own("pf,efd->ped", h.astype(jnp.bfloat16).astype(jnp.float32),
                   d_)

    def experts(impl):
        if impl == "pallas":
            return jax.jit(lambda xs, sizes, g_, u_, d_: grouped_expert_mlp(
                xs, sizes, 1, g_, u_, d_))(e_xs, e_sizes, gw, uw, dw)
        return jax.jit(every_expert)(e_xs, jnp.sort(chosen), gw, uw, dw)
    check(f"grouped_expert_mlp[bf16, {e_n} experts of {e_d} x {e_f}]", 3e-2,
          lambda: experts("pallas"), lambda: experts("xla"))

    # --- rms_norm, forward and (custom-VJP) backward, training shape.
    x = jax.random.normal(kq, (8, SEQ_LEN, d)).astype(jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(kk, (d,), jnp.float32)

    def rms(impl):
        def loss(x_, w_):
            y = rms_norm(x_, w_, eps=cfg.norm_eps, implementation=impl)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(x, w)
        return y, grads
    check("rms_norm fwd+bwd", 2e-2, lambda: rms("pallas"), lambda: rms(None))

    # --- the two flash wrappers, forward and backward, training shape.
    qa = jax.random.normal(kq, (8, SEQ_LEN, hq, hd)).astype(jnp.bfloat16)
    ka = jax.random.normal(kk, (8, SEQ_LEN, hkv, hd)).astype(jnp.bfloat16)
    va = jax.random.normal(kv, (8, SEQ_LEN, hkv, hd)).astype(jnp.bfloat16)
    ct = jax.random.normal(key, qa.shape).astype(jnp.bfloat16)

    def flash(impl):
        def loss(q_, k_, v_, ct_):
            out = flash_attention(q_, k_, v_, causal=True,
                                  implementation=impl)
            return jnp.sum((out * ct_).astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(qa, ka, va, ct)
        return out, grads
    for impl in ("splash", "pallas"):
        check(f"flash_attention[{impl}] fwd+bwd", 3e-2,
              lambda impl=impl: flash(impl), lambda: flash("xla"))

    # --- the train step really lowers to the kernel.
    try:
        from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
        from kubeflow_tpu.train.optimizers import OptimizerConfig
        from kubeflow_tpu.train.trainer import (
            build_train_step,
            init_state,
            state_shardings,
        )

        model = get_model(MODEL)
        mesh = build_mesh(MeshConfig())
        opt = OptimizerConfig(name="adafactor")
        state = jax.eval_shape(
            lambda: init_state(jax.random.PRNGKey(0), model, opt))
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            state, state_shardings(state, mesh, model))
        batch = {"tokens": jax.ShapeDtypeStruct(
            (8, SEQ_LEN + 1), jnp.int32,
            sharding=jax.NamedSharding(
                mesh, model.batch_partition_spec(model.config)))}
        text = build_train_step(model, opt, mesh).lower(
            state, batch).as_text()
        n = text.count("tpu_custom_call")
        print(f"train step lowering ({MODEL} seq{SEQ_LEN} on "
              f"{mesh.devices.size} chip(s)): {n} Mosaic custom call(s) "
              f"{'PASS' if n else 'FAIL'}", flush=True)
        if not n:
            failures.append("train step has no Mosaic custom call")
    except Exception:
        print(f"train step lowering: FAILED\n{traceback.format_exc()}",
              flush=True)
        failures.append("train step lowering")

    if failures:
        print(f"kernels: FAILED: {failures}", flush=True)
        return 1
    print("kernels: every shipped Pallas kernel compiled as written and "
          "matched its XLA twin", flush=True)
    return 0


def child_multichip() -> int:
    """One process owning every chip: the mesh builder on the shapes and
    subsets the system hands it, parameters really spread over four
    chips, and the one-chip loss the four-chip run is compared with."""
    import jax

    dev = _child_device()
    if dev["count"] < 4:
        print(f"multichip child needs 4 chips, has {dev['count']}")
        return 1
    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.parallel.mesh import (
        MESH_AXES,
        MeshConfig,
        build_mesh,
        serving_mesh,
    )
    from kubeflow_tpu.train import elastic
    from kubeflow_tpu.train.loop import RunConfig, run
    from kubeflow_tpu.train.optimizers import OptimizerConfig
    from kubeflow_tpu.train.trainer import init_state

    devices = jax.devices()
    # 1. create_device_mesh accepts the six-axis shapes on all four chips
    # and the device SUBSETS serving_mesh and the elastic path hand it.
    shapes = [dict(data=4), dict(fsdp=2, tensor=2), dict(data=2, tensor=2),
              dict(data=1, pipeline=2, tensor=2),
              dict(data=2, sequence=2), dict(data=2, expert=2)]
    for kw in shapes:
        mesh = build_mesh(MeshConfig(**kw), devices=devices[:4])
        assert mesh.axis_names == MESH_AXES and mesh.devices.size == 4, mesh
        assert len({d.id for d in mesh.devices.flat}) == 4, mesh
    for tp in (1, 2, 4):
        assert serving_mesh(tp).devices.size == tp
    for n in (1, 2, 4):
        build_mesh(elastic.scaled_mesh_config(MeshConfig(), n),
                   devices=devices[:n])
    print(f"mesh: {len(shapes)} six-axis shapes on 4 chips, serving_mesh "
          "tp=1/2/4 and elastic subsets of 1/2/4 devices all built")

    # 2. A parameter's shards land on four distinct chips, and every chip
    # holds bytes: code that has never seen a second chip may put
    # everything on the first.
    model = get_model(MODEL)
    run_cfg = json.loads(train_config(1, checkpoint_dir=None))
    opt = OptimizerConfig(**run_cfg.pop("optimizer"))
    state = init_state(jax.random.PRNGKey(0), model, opt, build_mesh(
        MeshConfig(**MULTICHIP_MESH), devices=devices[:4]))
    wq = state.params["layers"]["attn"]["wq"]
    holders = {s.device.id for s in wq.addressable_shards}
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    in_use = {d.id: d.memory_stats()["bytes_in_use"] for d in devices[:4]}
    print(f"sharding: wq {wq.shape} -> shards {shard_shapes} on devices "
          f"{sorted(holders)}; bytes_in_use {in_use}")
    assert len(holders) == 4, holders
    assert all(s.data.size * 4 == wq.size for s in wq.addressable_shards)
    assert all(v > 0 for v in in_use.values()), in_use
    del state, wq

    # 3. The first step of the same run on ONE chip of this host. The
    # loop has no option for fewer chips than the process sees, so this
    # goes in through the elastic path's device grant (mesh_source), in
    # process; the four-chip run itself is the parent's next child,
    # through the entry point.
    lines = []
    result = run(
        RunConfig(mesh=MeshConfig(), optimizer=opt, graceful_shutdown=False,
                  **run_cfg),
        log=lambda *a: (lines.append(" ".join(map(str, a))),
                        print(*a, flush=True)),
        mesh_source=lambda: 1)
    assert result["devices"] == 1, result
    print(f"{ONE_CHIP_LOSS} {losses_of(lines)[1]!r}", flush=True)
    return 0


def phase_multichip(count: int) -> bool:
    """False when skipped (and said so) for want of chips."""
    if count < 4:
        say(f"multichip: skipped: {count} device(s)")
        return False
    write_corpus()
    lines = run_child("multichip", [
        sys.executable, os.path.abspath(__file__), "--child", "multichip"],
        TIMEOUT_S["multichip"])
    device_of("multichip", lines)
    one_chip = float(next(ln for ln in reversed(lines) if ln.startswith(
        ONE_CHIP_LOSS))[len(ONE_CHIP_LOSS):])

    # The train loop through its entry point, the mesh from its JSON. A
    # step's loss is computed before its update, so the first logged
    # loss is comparable across meshes on the same seed and batch.
    steps = 3
    lines = run_child("multichip", [
        sys.executable, "-m", "kubeflow_tpu.train.loop",
        train_config(steps, mesh=MULTICHIP_MESH, checkpoint_dir=None)],
        TIMEOUT_S["train"])
    dev = device_of("multichip", lines)
    if not any(f"mesh={MULTICHIP_MESH}" in ln for ln in lines):
        raise PhaseFailed(f"multichip: the loop did not report "
                          f"mesh={MULTICHIP_MESH}")
    result = json.loads(lines[-1])
    if result["step"] != steps or result["devices"] != 4:
        raise PhaseFailed(f"multichip: ran {result['step']} steps on "
                          f"{result['devices']} device(s); wanted {steps} "
                          "on 4")
    losses = losses_of(lines)
    check_losses("multichip", losses, range(1, steps + 1))
    rel = abs(losses[1] - one_chip) / abs(one_chip)
    say(f"multichip: first-step loss fsdp2 x tensor2 {losses[1]:.4f} vs "
        f"one chip {one_chip:.4f} (rel {rel:.2e}, tol "
        f"{MESH_LOSS_RTOL:.0e}); 4-chip losses "
        f"{[losses[s] for s in sorted(losses)]} on {dev['count']} x "
        f"{dev['kind']}")
    if rel > MESH_LOSS_RTOL:
        raise PhaseFailed("multichip: the four-chip first-step loss is not "
                          "the one-chip loss")

    phase_serve(["--tp-shards", "4", "--kv-layout", "paged"],
                phase="multichip")
    say("multichip: ok")
    return True


# ---------------------------------------------------------------------------


def limit_file_size() -> int:
    """Lower this process's soft file-size limit to FILE_LIMIT_BYTES (or
    to a smaller limit already in force); children inherit it."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    limit = min(x for x in (FILE_LIMIT_BYTES, soft, hard)
                if x != resource.RLIM_INFINITY)
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))
    return limit


def probe() -> dict:
    """Which device there is — asked of a child, because the parent may
    not touch the backend."""
    lines = run_child("probe", [
        sys.executable, os.path.abspath(__file__), "--child", "probe"], 180)
    return device_of("probe", lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of: " + ", ".join(PHASES))
    ap.add_argument("--child", nargs=argparse.REMAINDER,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        name, rest = args.child[0], args.child[1:]
        if name == "traced":
            return child_traced(rest[0], rest[1:])
        if name == "probe":
            _child_device()
            return 0
        return {"kernels": child_kernels, "multichip": child_multichip,
                "corpus": child_corpus}[name]()

    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phase(s) {sorted(unknown)}")
    os.makedirs(WORK, exist_ok=True)
    say(f"file-size limit for every child: {limit_file_size() >> 20} MiB")
    t_start = time.perf_counter()
    devices, serve_tokens, skipped = [], None, []
    try:
        if "kernels" not in phases:
            # Whatever runs first must be a child that refuses anything
            # but a TPU; the kernels child is one.
            devices.append(probe())
        if "kernels" in phases:
            lines = run_child("kernels", [
                sys.executable, os.path.abspath(__file__), "--child",
                "kernels"], TIMEOUT_S["kernels"])
            devices.append(device_of("kernels", lines))
            say("kernels: ok")
        if "train" in phases:
            devices.append(phase_train())
        if "serve" in phases:
            dev, serve_tokens = phase_serve()
            devices.append(dev)
        if "cache" in phases:
            if not os.path.isdir(os.path.join(WORK, "ckpt",
                                              str(TRAIN_STEPS))):
                raise PhaseFailed("cache: needs the train phase's "
                                  "checkpoint; run the train phase first")
            devices.append(phase_cache(serve_tokens))
        if "multichip" in phases:
            if not phase_multichip(devices[-1]["count"]):
                skipped.append("multichip")
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for proc in _children:
            _kill(proc)
    if any(d != devices[0] for d in devices):
        print(f"chip_smoke: FAILED — phases disagree about the device: "
              f"{devices}", file=sys.stderr)
        return 1
    dev = devices[0]
    say(f"chip_smoke: {', '.join(p for p in phases if p not in skipped)} "
        f"passed{''.join(f', {p} skipped' for p in skipped)} in "
        f"{time.perf_counter() - t_start:.0f}s on {dev['count']} x "
        f"{dev['kind']} (jax {dev['jax']})")
    out = {"ok": True, "device": {"platform": dev["platform"],
                                  "kind": dev["kind"],
                                  "count": dev["count"]}}
    if phases != list(PHASES):
        out["phases"] = phases  # a partial run says so
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
