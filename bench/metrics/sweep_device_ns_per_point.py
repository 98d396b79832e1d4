"""Device time per grid point of the traced sweeps: the union of the
device-op intervals within the window, summed over the cell's devices,
over the grid points of the window's sweeps."""


def read(layer, trace):
    if layer["kind"] != "sweep" or trace is None or not layer["points"]:
        return None
    return trace["busy_s_sum"] / layer["points"] * 1e9
