"""Device time per scored request: the busy union of the traced window,
summed over the cell's devices, over the requests the batcher scored."""


def read(layer, trace):
    if layer["kind"] != "open_loop" or trace is None or not layer["requests"]:
        return None
    return trace["busy_s_sum"] / layer["requests"] * 1e6
