"""Requests per scored batch over the window, from the server's own
``batches`` and ``batched_requests`` counters."""


def read(layer, trace):
    if layer["kind"] != "open_loop":
        return None
    return layer["mean_batch"]
