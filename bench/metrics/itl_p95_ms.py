"""itl_p95_ms: 95th percentile (nearest rank) of the gaps between a request's
consecutive tokens, over every gap whose later token was stamped inside the
window (ms, host clock).  A token's stamp is the end of the tick that made it.

Under open-loop chat some 4-6% of the gaps span a padded prefill tick, so
this percentile reads a decode tick, a prefill tick or a value between,
depending on how the seed orders arrivals and lengths."""


def read(run):
    gaps = [(b - a) * 1e3 for r in run.rec.reqs.values()
            for a, b in zip(r.stamps, r.stamps[1:]) if run.in_window(b)]
    return run.quantile(gaps, 0.95) if gaps else None
