"""Share of the traced serving window in which no operation ran on the
device, averaged over the cell's devices."""


def read(layer, trace):
    if layer["kind"] != "open_loop" or trace is None:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
