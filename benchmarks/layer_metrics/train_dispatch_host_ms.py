"""What the host adds to each ``fit_scan`` dispatch: the traced window's wall
time less the device's busy time in it, per dispatch."""


def read(trace, cell, window, peaks):
    if trace is None or not trace.busy_s or not window["dispatches"]:
        return None
    return {"value": 1e3 * (window["wall_s"] - trace.busy_s) / window["dispatches"]}
