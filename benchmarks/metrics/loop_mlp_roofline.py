"""Kernels, a looped stack's SwiGLU in decode: its matrices once a pass,
over the device time under the ``mlp`` scope inside ``decode``."""


def read(run):
    from benchmarks.harness import loop

    marks = loop.traced(run)
    if marks is None:
        return None
    return loop.roofline(
        run, loop.mlp_bytes(run["config"], marks["decode_steps"]),
        loop.scope_seconds(run, ("mlp",)))
