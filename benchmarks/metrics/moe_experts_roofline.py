"""Kernels, the expert layer's products in decode: the routed experts the
traced steps touched (each once a step) and the shared experts, over the
device time under the ``moe_experts`` scope inside ``decode`` and of the
grouped products' ragged-dot kernels, which carry no scope
(harness/latent_moe.py:RAGGED_DOT)."""


def read(run):
    from benchmarks.harness import latent_moe

    marks = latent_moe.traced(run)
    if marks is None:
        return None
    return latent_moe.roofline(run, latent_moe.expert_bytes(
        run["config"], marks["decode_steps"], marks["experts_touched"]),
        latent_moe.scope_seconds(run, ("moe_experts",),
                                 unscoped_ops=(latent_moe.RAGGED_DOT,)))
