"""Scheduler: 95th percentile of the wait from a request's due time to its
admission into a decode slot (``RequestMetrics.admit_t``), over the
window's finished requests."""

SOURCE = "program_span"


def applies(cell):
    return cell.traffic["driver"] == "serve"


def read(out):
    from chipbench import stats

    waits = [r["admit"] - r["due"] for r in out["window"].requests
             if r["ok"]]
    return stats.percentile(waits, 0.95) * 1e3 if waits else None
