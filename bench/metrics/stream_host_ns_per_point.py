"""Host time of the streaming pipeline per grid point: the sweep profile's
``enumerate_s`` + ``reduce_s`` (decode, constraint mask and reducer folds)
over the grid points.  The fused device path does both inside its step and
reports nothing here."""


def read(layer, trace):
    if layer["kind"] != "sweep" or not layer["points"]:
        return None
    prof = layer["profile"]
    if prof.get("path") != "host-stream":
        return None
    return (prof["enumerate_s"] + prof["reduce_s"]) / layer["points"] * 1e9
