"""Every `python -m kubeflow_tpu.X` command the manifest layer renders must
be a real module whose CLI parses (the operator-image contract: the
Deployment command is an actual binary,
kubeflow/tf-training/tf-job-operator.libsonnet:99-143). And every program
the README and the verify notes tell a reader to run must exist.
"""

from __future__ import annotations

import importlib.util
import os
import re
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from kubeflow_tpu.manifests.core import REQUIRED, all_prototypes


def _dummy_value(spec):
    if spec.default is not REQUIRED:
        return spec.default
    by_name = {
        "name": "x", "namespace": "kubeflow", "model_path": "/m",
        "input_path": "/in.jsonl", "output_path": "/out.jsonl",
        "target_url": "http://svc/healthz",
    }
    return by_name.get(spec.name, "x")


def _all_rendered_commands() -> set[tuple[str, ...]]:
    commands: set[tuple[str, ...]] = set()

    def walk(node):
        if isinstance(node, dict):
            cmd = node.get("command")
            if (isinstance(cmd, list) and len(cmd) >= 3
                    and cmd[0] == "python" and cmd[1] == "-m"):
                commands.add((cmd[2], *node.get("args", [])))
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    for name, proto in all_prototypes().items():
        params = {p.name: _dummy_value(p) for p in proto.params}
        for obj in proto.generate(params):
            walk(obj)
    return commands


COMMANDS = sorted(_all_rendered_commands())


def test_found_the_known_entrypoint_surface():
    modules = {c[0] for c in COMMANDS}
    # The full set VERDICT round 1 flagged as missing, plus round-1 survivors.
    assert {
        "kubeflow_tpu.operators",
        "kubeflow_tpu.operators.notebook",
        "kubeflow_tpu.operators.profile",
        "kubeflow_tpu.operators.study",
        "kubeflow_tpu.operators.benchmark",
        "kubeflow_tpu.gateway",
        "kubeflow_tpu.dashboard",
        "kubeflow_tpu.dashboard.training",
        "kubeflow_tpu.auth.gatekeeper",
        "kubeflow_tpu.auth.webhook",
        "kubeflow_tpu.webapps.jupyter",
        "kubeflow_tpu.webapps.study",
        "kubeflow_tpu.observability.collector",
        "kubeflow_tpu.tuning.service",
        "kubeflow_tpu.serving",
        "kubeflow_tpu.serving.batch_predict",
        "kubeflow_tpu.utils.echo_server",
        "kubeflow_tpu.utils.usage_reporter",
        "kubeflow_tpu.workloads.tf_cnn",
        "kubeflow_tpu.workloads.torch_xla_ddp",
        "kubeflow_tpu.workloads.allreduce_smoke",
        "kubeflow_tpu.workloads.allreduce_bench",
    } <= modules


@pytest.mark.parametrize("module", sorted({c[0] for c in COMMANDS}))
def test_rendered_module_exists(module):
    # `python -m pkg` runs pkg/__main__.py; `python -m pkg.mod` runs mod.
    spec = importlib.util.find_spec(module)
    assert spec is not None, f"manifests reference missing module {module}"
    if spec.submodule_search_locations is not None:  # a package → needs __main__
        assert importlib.util.find_spec(module + ".__main__") is not None, (
            f"package {module} has no __main__"
        )


def test_every_rendered_command_parses_help():
    """`python -m <mod> --help` must exit 0 for every rendered command."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)

    def run_help(cmd):
        module = cmd[0]
        proc = subprocess.run(
            [sys.executable, "-m", module, "--help"],
            capture_output=True, text=True, timeout=120, env=env,
        )
        return module, proc

    modules = sorted({c[0] for c in COMMANDS})
    with ThreadPoolExecutor(max_workers=8) as pool:
        results = list(pool.map(run_help, [(m,) for m in modules]))
    failures = [
        f"{module}: rc={proc.returncode}\n{proc.stderr[-500:]}"
        for module, proc in results if proc.returncode != 0
    ]
    assert not failures, "\n\n".join(failures)


_SENTINEL = "--cc-unknown-sentinel"


def test_rendered_args_are_accepted_by_each_parser():
    """Run every rendered command with its exact manifest args plus an
    unknown sentinel option. argparse collects ALL unrecognized optionals
    and lists them in one error — so the expected outcome is rc 2 naming
    ONLY the sentinel. A renamed/removed real flag shows up next to it
    (a trailing --help can't catch this: its action fires before
    unknown-option validation, masking bogus rendered args that would
    CrashLoop the Deployment at container start)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)

    def run_cmd(cmd):
        module, *args = cmd
        proc = subprocess.run(
            [sys.executable, "-m", module, *args, _SENTINEL],
            capture_output=True, text=True, timeout=120, env=env,
        )
        return cmd, proc

    with ThreadPoolExecutor(max_workers=8) as pool:
        results = list(pool.map(run_cmd, COMMANDS))
    failures = []
    for cmd, proc in results:
        unrecognized = [
            line for line in proc.stderr.splitlines()
            if "unrecognized arguments" in line
        ]
        ok = (proc.returncode == 2 and unrecognized
              and all(
                  line.split("unrecognized arguments:")[1].strip()
                  == _SENTINEL for line in unrecognized
              ))
        if not ok:
            failures.append(f"{' '.join(cmd)}: rc={proc.returncode}\n"
                            f"{proc.stderr[-500:]}")
    assert not failures, "\n\n".join(failures)


REPO = Path(__file__).resolve().parent.parent
_FENCE = re.compile(r"^```[^\n]*\n(.*?)^```", re.M | re.S)
_RUN = re.compile(r"\bpython3?\s+(-m\s+)?([\w./-]+)")


@pytest.mark.parametrize("doc", ["README.md", ".claude/skills/verify/SKILL.md"])
def test_documented_commands_name_programs_that_exist(doc):
    """Each `python <file>.py` and `python -m <module>` inside a fenced
    block names a file of the repo or an importable module: a reader is
    never sent to a program that was deleted."""
    named = [(bool(m.group(1)), m.group(2))
             for block in _FENCE.findall((REPO / doc).read_text())
             for m in _RUN.finditer(block)
             if m.group(1) or m.group(2).endswith(".py")]
    assert named, f"{doc}: no command found; did the fences change?"
    missing = [target for is_module, target in named
               if not (importlib.util.find_spec(target) if is_module
                       else (REPO / target).exists())]
    assert not missing, f"{doc} names programs that do not exist: {missing}"
