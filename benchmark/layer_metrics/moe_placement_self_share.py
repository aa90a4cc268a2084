"""moe_placement_self_share — the expert layer's sort, placement,
add-back and bound, by self time with what they adopted
(benchmark/self_time.py), apart from the expert function and the
grouped products."""

import self_time


def read(run, spec):
    found = self_time.table(run)
    if found is None or not any(s in found["by_scope_s"]
                                for s in spec["needs_one_of"]):
        return None
    return self_time.share(found, spec["placement_scopes"])
