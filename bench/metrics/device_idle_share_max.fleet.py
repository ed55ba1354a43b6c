"""The largest idle share of the cell's devices in the traced window:
1 - busy / window for each device, busy being the union of its
operations' intervals; a device that ran nothing reads 1."""


def read(run):
    trace = run.trace
    if trace is None or not trace.get("window_s"):
        return None
    busy = trace["devices_busy_s"]
    n_devices = int(run.cell.config["engine"].get("n_devices", 1))
    if len(busy) < n_devices:
        return 1.0
    return 1.0 - min(busy) / trace["window_s"]
