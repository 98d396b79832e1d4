"""Host time a sweep spends setting up and tearing down, per grid point:
the sweep profile's ``plan_s`` + ``open_s`` + ``close_s`` (the program
spans ``repro.sweep.plan``, ``.open`` and ``.close``: the plan and the
device tables, their upload and the carry, the state pull, the reducer
merge and the report) over the grid points.  The host stream has no
``open``.  Nothing when the program reports no such spans."""


def read(layer, trace):
    if layer["kind"] != "sweep" or not layer["points"]:
        return None
    prof = layer["profile"]
    if "plan_s" not in prof or "close_s" not in prof:
        return None
    edges = prof["plan_s"] + prof.get("open_s", 0.0) + prof["close_s"]
    return edges / layer["points"] * 1e9
