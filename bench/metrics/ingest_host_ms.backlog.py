"""Host ms per window spent in the delivery calls (``translate_batch`` and
``publish`` of every environment and stream) inside the measured window."""


def read(run):
    rec = run.record
    spans = [d[3] - d[2] for d in rec.deliveries if d[2] >= rec.t0]
    return 1e3 * sum(spans) / len(spans) if spans else None
