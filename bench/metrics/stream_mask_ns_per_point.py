"""Host time of the streaming pipeline's constraint mask per grid point:
the sweep profile's ``mask_s`` (span ``repro.chunk.mask``, each chunk's
feasibility mask) over the grid points.  Nothing off the host stream, or
when the program reports no such span."""


def read(layer, trace):
    if layer["kind"] != "sweep" or not layer["points"]:
        return None
    prof = layer["profile"]
    if prof.get("path") != "host-stream" or "mask_s" not in prof:
        return None
    return prof["mask_s"] / layer["points"] * 1e9
