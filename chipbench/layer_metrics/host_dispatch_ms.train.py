"""Entry points (gluon.TrainStep): host time of one ``step(*batch)`` call
until it returns, i.e. the enqueue, median over the window's steps."""
import statistics


def read(trace, run):
    if not run.get("dispatch_s"):
        return None
    return statistics.median(run["dispatch_s"]) * 1e3
