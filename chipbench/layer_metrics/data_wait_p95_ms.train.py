"""Input pipeline (gluon.data.DataLoader): the 95th percentile of the
program's span ``dataloader_next`` over the window's steps -- a starved
step is a tail event, which the median of ``data_wait_ms.train`` hides."""
import statistics

import program_spans


def read(trace, run):
    waits = program_spans.ring(run).get("dataloader_next")
    if not waits or len(waits) < 2:
        return None
    # inclusive: never beyond the longest wait, however few the steps
    return statistics.quantiles(waits, n=20, method="inclusive")[18] * 1e3
