"""Cache: tokens the sparse layers' selection attended over the tokens the
rows held, per row and decode step across the window, from the decoder's
counters (100 while every row is at or under ``dense_len``)."""


def read(run):
    c = run.get("counters")
    if not c or not c.get("sparse_tokens_in_context"):
        return None
    return 100.0 * c["sparse_tokens_attended"] / c["sparse_tokens_in_context"]
