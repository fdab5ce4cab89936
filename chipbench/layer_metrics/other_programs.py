"""Compile: how many programs are behind ``other_programs_s`` -- its
``xla.backend`` records, one per executable loaded or built."""
import startup_spans


def read(trace, run):
    recs = startup_spans.other_programs(run)
    if recs is None:
        return None
    return sum(r["name"] == "xla.backend" for r in recs)
