"""attn_norm_rope_self_share — q's and k's head norms and RoPE between
the projections and the attention core: self time, own and adopted
(benchmark/self_time.py), under `kps.attn.norm_rope`."""

import self_time


def read(run, spec):
    found = self_time.table(run)
    if found is None or spec["scope"] not in found["by_scope_s"]:
        return None
    line = found["by_scope_s"][spec["scope"]]
    per = 1e3 / found["updates"]
    counters = (getattr(run.app, "last_run", None) or {}).get("counters") or {}
    counted = ", ".join(f"{name} {counters[name]}"
                        for name in spec["counters"] if name in counters)
    print(f"[bench] attn_norm_rope_self_share: under {spec['scope']} "
          f"{line['own'] * per:.4f} ms own + {line['adopted'] * per:.4f} ms "
          f"adopted an update of {found['period_s'] * 1e3:.4f}"
          + (f"; {counted}" if counted else ""), flush=True)
    return self_time.share(found, [spec["scope"]])
