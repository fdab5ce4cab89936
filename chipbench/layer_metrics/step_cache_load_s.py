"""Compile: loading the whole-step program from the persistent cache --
the program's records ``xla.cache_load`` with ``fun`` = ``whole_step``
(the program pairs each load with the backend record it belongs to),
summed over set-up; 0 when every fetch of the step was a build (nothing
was loaded: ``step_programs_obtained`` and ``xla_programs_compiled`` say
so), None on a program without the records."""
import startup_spans


def read(trace, run):
    if not startup_spans.step_stage(run, "backend"):
        return None
    return sum(r["dur"] for r in startup_spans.step_stage(run, "cache_load"))
