"""Host time of the streaming pipeline's pull stage per grid point: the
sweep profile's ``pull_s`` (span ``repro.chunk.pull``, the host blocked on
each chunk's columns, the device's wait included) over the grid points.
Nothing off the host stream, or when the program reports no such span."""


def read(layer, trace):
    if layer["kind"] != "sweep" or not layer["points"]:
        return None
    prof = layer["profile"]
    if prof.get("path") != "host-stream" or "pull_s" not in prof:
        return None
    return prof["pull_s"] / layer["points"] * 1e9
