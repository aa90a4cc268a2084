"""fused_host_exposed_share — device idle time under this layer's spans of the
dispatching thread (benchmark/span_reduce.py)."""

import span_reduce


def read(run, spec):
    return span_reduce.idle_share(run, spec)
