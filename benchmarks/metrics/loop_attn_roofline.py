"""Kernels, a looped stack's attention in decode: the attention matrices
once a pass and K/V of the attended tokens over every cache layer, over
the device time under the ``attn`` scope inside ``decode``. It reads low
while a step reads its dense store whole whatever is live."""


def read(run):
    from benchmarks.harness import loop

    marks = loop.traced(run)
    if marks is None:
        return None
    return loop.roofline(run, loop.attn_bytes(
        run["config"], marks["decode_steps"], marks["kv_tokens_attended"]),
        loop.scope_seconds(run, ("attn",)))
