"""Expert layers: rows of the busiest held expert over the mean of the
held experts' rows in the window's last step, averaged over the layers —
the program's gauge ``moe_expert_load_max_over_mean``, produced on the
device and read once after the window (the driver's ``moe_load``)."""


def read(trace, run):
    load = run.get("moe_load")
    if not load:
        return None
    return sum(ratio for _rows, ratio in load.values()) / len(load)
