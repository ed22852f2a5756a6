"""Kernels, the latent read in decode: the latent and the rotary key of
every attended token in every layer (the padding of the store's row not
counted), over the device time under the ``latent_attn`` scope inside
``decode`` (the read and the absorbed projections around it)."""


def read(run):
    from benchmarks.harness import latent_moe

    marks = latent_moe.traced(run)
    if marks is None:
        return None
    return latent_moe.roofline(run, latent_moe.latent_bytes(
        run["config"], marks["kv_tokens_attended"]),
        latent_moe.scope_seconds(run, ("latent_attn",)))
