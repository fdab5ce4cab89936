"""Input pipeline (gluon.data.DataLoader): host time the training loop
waits for its next batch -- the program's span ``dataloader_next``
(workers' results received, collated and placed on the device); median
over the window's steps, from the program's span ring."""
import statistics

import program_spans


def read(trace, run):
    waits = program_spans.ring(run).get("dataloader_next")
    return None if not waits else statistics.median(waits) * 1e3
