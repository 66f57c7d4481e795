"""Sums of the trainer's own spans and counters over the window's LC
iterations, for the per-layer readers in ``metrics/``.

Each record of ``LCTrainer.history`` carries ``host_ms`` (host
milliseconds per span name) and ``counts`` (spans and counters per name)
of its own iteration. A program whose records lack them gives None."""


def sums(ctx, host=(), counts=()):
    """``(host_ms sums, count sums, records)`` of the names asked for,
    over the window's records; None when there is no record or a record
    lacks one of the names."""
    recs = ctx.get("history") or []
    if not recs or any(n not in r.get("host_ms", {}) for r in recs for n in host) \
            or any(n not in r.get("counts", {}) for r in recs for n in counts):
        return None
    return ({n: sum(r["host_ms"][n] for r in recs) for n in host},
            {n: sum(r["counts"][n] for r in recs) for n in counts},
            len(recs))
