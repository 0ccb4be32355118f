"""Share of the window's requests answered correctly within their deadline,
counted from the due time; failed and unanswered requests count as missed.
Host clock.  Cells whose traffic sets no deadline read nothing."""


def read(run):
    if not run.records or run.records[0].deadline_s is None:
        return None
    met = sum(1 for r in run.records if r.failure is None
              and r.latency_s is not None and r.latency_s <= r.deadline_s)
    return 100.0 * met / len(run.records)
