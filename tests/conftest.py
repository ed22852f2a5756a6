"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the "fake slice" — SURVEY.md §4:
the multi-node-without-hardware capability the reference lacks). The env vars
must be set before jax is first imported anywhere in the test process.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Tests never take the accelerator, even on a machine that has one.
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

from kubeflow_tpu.k8s.fake import FakeApiServer  # noqa: E402


@pytest.fixture()
def api():
    """A fresh fake apiserver with the kubeflow namespace present."""
    server = FakeApiServer()
    server.ensure_namespace("kubeflow")
    server.ensure_namespace("default")
    return server
