"""slow_slice_share — how many of the window's slices lie in the second
mode of their times, from the harness's own samples."""

import statistics


def read(run, spec):
    times = run.call_times
    if len(times) < 2:
        return None               # one call a window: nothing to share
    edge = spec["over_median"] * statistics.median(times)
    slow = sum(t > edge for t in times)
    print(f"[bench] slow_slice_share: {slow} of {len(times)} slices over "
          f"{1e3 * edge:.1f} ms", flush=True)
    return 100.0 * slow / len(times)
