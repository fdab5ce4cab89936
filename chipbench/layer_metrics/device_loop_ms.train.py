"""Device: time per traced step of the operations inside the looped
stack's rolled loop — the program's scope ``ut_step``, opened in the body
of `model_zoo.decoder.run_looped`'s scan: the layers of every loop step
and the final norm that closes each pass, forward, recomputed forward and
backward together.

The scope is matched as the substring ``ut_step/``, not ``/ut_step/``:
inside a scan's body part of the instructions carry op_names that start
at ``ut_step/...`` with no ``jit(whole_step)/...`` in front.  The loop's
own ``while`` instruction, whose event spans everything that ran inside
it, reads ``.../while`` without ``ut_step`` and so stays out: the loop is
not counted twice.  None on a program without the scope."""
import program_spans


def read(trace, run):
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: "ut_step/" in s) or None
