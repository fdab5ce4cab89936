"""Entry points (gluon.TrainStep): device idle time per traced step that
falls inside the compiled call, i.e. overlaps the program's
``mxtpu:whole_step`` annotation in the trace; what is left of
``device_idle_pct.train`` is idle behind the Python around the call."""
import program_spans


def read(trace, run):
    xplane = program_spans.xplane_of(run)
    if xplane is None or not run.get("traced_steps"):
        return None
    idle_s = program_spans.idle_under(
        program_spans.devices_of(xplane, run["platform"]),
        program_spans.host_spans(xplane), "whole_step")
    return None if idle_s is None else idle_s * 1e3 / run["traced_steps"]
