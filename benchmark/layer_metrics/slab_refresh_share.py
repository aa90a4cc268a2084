"""slab_refresh_share — the upload at the head of the window's call,
from the program's own record of its last drive call."""


def read(run, spec):
    last = getattr(run.app, "last_run", None)
    if not last or last.get("path") != "fused" or last["seconds"] <= 0:
        return None
    print(f"[bench] slab_refresh_share: last_run {last}", flush=True)
    return 100.0 * last["slab_refresh_s"] / last["seconds"]
