"""Host time a sweep spends merging the chips' folded states into its
reducers, per grid point: the sweep profile's ``merge_s`` (span
``repro.sweep.merge``, inside ``repro.sweep.close`` on the fused device
path: the chips' carries, pulled, merged in chip order) over the grid
points.  Nothing when the program reports no such span."""


def read(layer, trace):
    if layer["kind"] != "sweep" or not layer["points"]:
        return None
    prof = layer["profile"]
    if "merge_s" not in prof:
        return None
    return prof["merge_s"] / layer["points"] * 1e9
