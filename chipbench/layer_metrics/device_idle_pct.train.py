"""Device: share of the traced steps in which no operation ran."""


def read(trace, run):
    if not run.get("traced_steps"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
