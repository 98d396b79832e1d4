"""Host time of the streaming pipeline's pack stage per grid point: the
sweep profile's ``pack_s`` (span ``repro.chunk.pack``, each chunk's
expansion into a ``GroupBatch``) over the grid points.  Nothing off the
host stream, or when the program reports no such span."""


def read(layer, trace):
    if layer["kind"] != "sweep" or not layer["points"]:
        return None
    prof = layer["profile"]
    if prof.get("path") != "host-stream" or "pack_s" not in prof:
        return None
    return prof["pack_s"] / layer["points"] * 1e9
