"""fused_call_edge_share — the flat parameter vector up where the
window's call begins and down where it ends, from the program's own
record of its last drive call."""

EDGES = ("theta_up_s", "device_wait_s", "theta_down_s")


def read(run, spec):
    last = getattr(run.app, "last_run", None)
    if (not last or last.get("path") != "fused" or last["seconds"] <= 0
            or not all(k in last for k in EDGES)):
        return None
    up, wait, down = (last[k] for k in EDGES)
    print(f"[bench] fused_call_edge_share: theta_up_s {up:.6f} + "
          f"theta_down_s {down:.6f} of the call's {last['seconds']:.6f}s; "
          f"device_wait_s {wait:.6f}", flush=True)
    return 100.0 * (up + down) / last["seconds"]
