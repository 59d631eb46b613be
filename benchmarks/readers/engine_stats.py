"""Readers of the serving engine's own counters: ``LLMEngine.stats()``
as the runner already fetches it at the window's middle and at its end
(``facts["backlog"]["mid"]`` / ``["end"]``, printed in every run's note).
Both read the DIFFERENCE end - mid, the window's second half: what the
engine counted for the requests it handled there, after the traced stretch
has ended. Where the program publishes no such key (a commit before the
counters existed) a reader returns None and the metric is left out."""

from benchmarks.common import reader


def _mid_end(facts, *keys):
    """The two snapshots, or None where either lacks one of ``keys``."""
    snaps = facts.get("backlog") or {}
    mid, end = snaps.get("mid") or {}, snaps.get("end") or {}
    if any(k not in mid or k not in end for k in keys):
        return None
    return mid, end


@reader("stats_delta_hist_percentile")
def stats_delta_hist_percentile(facts, params):
    """The ``q``-th percentile of histogram ``hist`` over the requests
    observed between the two snapshots: the bucket that holds rank
    ``q/100 * n``, linear inside it (the first bucket starts at 0; the
    last has no upper edge and reads as its lower one). None on an empty
    difference."""
    snaps = _mid_end(facts, params["hist"], "hist_bounds_ms")
    if snaps is None:
        return None
    mid, end = snaps
    bounds = end["hist_bounds_ms"]
    counts = [e - m for e, m in zip(end[params["hist"]]["counts"],
                                    mid[params["hist"]]["counts"])]
    n = sum(counts)
    if n <= 0:
        return None
    rank, below = params["q"] / 100.0 * n, 0
    for i, c in enumerate(counts):  # rank <= n: some bucket reaches it
        if c and below + c >= rank:
            break
        below += c
    if i == len(bounds):
        return float(bounds[-1])
    lo = bounds[i - 1] if i else 0.0
    return lo + (bounds[i] - lo) * (rank - below) / c


@reader("stats_delta_ratio")
def stats_delta_ratio(facts, params):
    """``scale * sum(num) / sum(den)`` over the differences of the named
    keys; a key written ``-name`` is subtracted. None where a key is
    missing or the divisor is 0."""
    num, den = ([(-1.0 if k.startswith("-") else 1.0, k.lstrip("-"))
                 for k in params[side]] for side in ("num", "den"))
    snaps = _mid_end(facts, *(k for _sign, k in num + den))
    if snaps is None:
        return None
    mid, end = snaps

    def total(terms):
        return sum(sign * (end[k] - mid[k]) for sign, k in terms)

    if not total(den):
        return None
    return params.get("scale", 1.0) * total(num) / total(den)
