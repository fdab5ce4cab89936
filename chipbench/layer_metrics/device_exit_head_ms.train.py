"""Device: time per traced step of the exits' side of a looped model — the
operations under the program's scopes ``lm_head`` (every exit's logits and
per-token cross-entropy, by blocks of positions), ``exit_gate`` (the
gate's logits) and ``exit_loss`` (the exit distribution, the expected
loss and its entropy term); forward, a block's recomputed forward and
backward together.  Matched as substrings without a leading slash: the
head's blocks run in the body of a rolled loop, where part of the
instructions' op_names start at the scope itself.  That loop's own
``while`` instruction reads ``.../lm_head/while`` and lasts as long as
everything that ran inside it: it is left out, or the head would count
twice.  None on a program without ``exit_loss`` (another model's
``lm_head`` alone is not this metric's)."""
import program_spans

PARTS = ("lm_head/", "exit_gate/", "exit_loss/")


def read(trace, run):
    scopes = program_spans.op_scopes() or {}
    if not any("exit_loss/" in s for s in scopes.values()):
        return None
    return program_spans.per_traced_step_ms(
        trace, run, lambda s: any(p in s for p in PARTS)
        and not s.endswith("/while")) or None
