"""attention_share — latent attention's part of the solver programs'
device time, by named scope (benchmark/span_reduce.py)."""

import span_reduce


def read(run, spec):
    return span_reduce.scope_share(run, spec)
