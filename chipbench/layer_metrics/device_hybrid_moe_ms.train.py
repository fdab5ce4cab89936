"""Device: time per traced step of the expert layers of a stack whose
first layers are dense — the operations under the program's
``moe.router``, ``moe.dispatch``, ``moe.experts`` and ``moe.combine``
scopes, and the grouped products themselves, which XLA expands into
kernels of its own name (``ragged-dot*``) outside any scope: what
``device_moe_ms.train`` reads, in the cell whose configuration chooses its
layers one by one (`kernel_counts_hybrid.applies`); None on any other."""
import kernel_counts_hybrid
import program_spans


def read(trace, run):
    scopes = program_spans.op_scopes()
    if (not scopes or not trace.get("op_s") or not run.get("traced_steps")
            or not kernel_counts_hybrid.applies(run["cfg"])):
        return None
    total = sum(s for name, s in trace["op_s"].items()
                if "/moe." in scopes.get(name, "")
                or name.startswith("ragged-dot"))
    return total * 1e3 / run["traced_steps"] if total else None
