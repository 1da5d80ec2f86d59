"""Load generator: 99th percentile of (send time - due time) per message."""
import numpy as np


def read(ctx):
    run = ctx["run"]
    sent = ~np.isnan(run.sent_at)
    if not sent.any():
        return None
    late = run.sent_at[sent] * 1000.0 - run.timed.due_ms[sent]
    return float(np.percentile(late, 99))
