"""Device: time per traced step of the routed part of the expert layers —
the operations under the program's ``moe.router``, ``moe.dispatch``,
``moe.experts`` and ``moe.combine`` scopes, and the grouped products
themselves, which XLA expands into kernels of its own name
(``ragged-dot*``) outside any scope.  The shared expert (``moe.shared``)
is not in it, and the conditionals' own events carry none of these
scopes, so nothing is summed twice."""
import program_spans

ROUTED = ("/moe.router/", "/moe.dispatch/", "/moe.experts/", "/moe.combine/")


def read(trace, run):
    scopes = program_spans.op_scopes()
    if not scopes or not trace.get("op_s") or not run.get("traced_steps"):
        return None
    total = sum(s for name, s in trace["op_s"].items()
                if any(part in scopes.get(name, "") for part in ROUTED)
                or name.startswith("ragged-dot"))
    return total * 1e3 / run["traced_steps"] if total else None
