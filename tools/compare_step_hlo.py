#!/usr/bin/env python3
"""Are two optimized HLO texts of a whole step the same program?

    JAX_PLATFORMS=cpu python3 tools/compare_step_hlo.py parent.hlo change.hlo

The texts are what `chipbench/compile_check_large.py --hlo <file>` keeps.
Two checkouts of an equal program still differ in their source locations:
the metadata's files and lines, and the debug locations inside each Mosaic
kernel's serialised body.  So the text is compared line for line with the
metadata's locations and the kernels' bodies cut out, and the kernels are
compared as MLIR printed without debug information.  Exit code 0: equal.

A change that gives a kernel one more operand renumbers every instruction
after it, and then most lines differ by a number alone.  So the lines are
also compared as a multiset with the instructions' numbers stripped
(`%fusion.123` -> `%fusion`): what is left is what the change touched,
printed with ``--show``.
"""
import base64
import collections
import re
import sys

_LOCATION = re.compile(
    r'source_file="[^"]*"|source_(?:end_)?(?:line|column)=\d+'
    r'|stack_frame_id=\d+')
_TABLE = re.compile(
    r"(?ms)^(?:FileNames|FunctionNames|FileLocations|StackFrames).*?^\n")
_BODY = re.compile(r'"body":"([^"]+)"')
_NUMBER = re.compile(r"(%[A-Za-z_\-]+)(?:\.\d+)+")


def outside_kernels(text):
    """The text's lines without source locations and kernel bodies."""
    text = _TABLE.sub("", _LOCATION.sub("", text))
    return _BODY.sub('"body":""', text).splitlines()


def kernels(text):
    """{a kernel's MLIR without debug locations: how many calls}."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    out = collections.Counter()
    with ctx:
        for body in _BODY.findall(text):
            module = ir.Module.parse(base64.b64decode(body))
            out[module.operation.get_asm(enable_debug_info=False)] += 1
    return out


def unnumbered(lines):
    """The lines as a multiset, instruction numbers stripped."""
    return collections.Counter(_NUMBER.sub(r"\1", line) for line in lines)


def main(a, b, show=False):
    with open(a) as f, open(b) as g:
        ta, tb = f.read(), g.read()
    la, lb = outside_kernels(ta), outside_kernels(tb)
    differing = sum(x != y for x, y in zip(la, lb)) + abs(len(la) - len(lb))
    ka, kb = kernels(ta), kernels(tb)
    print(f"lines {len(la)} / {len(lb)}, differing outside kernel bodies: "
          f"{differing}; Mosaic calls {sum(ka.values())} / "
          f"{sum(kb.values())}, distinct kernels {len(ka)} / {len(kb)}, "
          f"{'equal' if ka == kb else 'DIFFERENT'}")
    if differing:
        ua, ub = unnumbered(la), unnumbered(lb)
        only_a, only_b = ua - ub, ub - ua
        print(f"numbers stripped: {sum(only_a.values())} lines only in the "
              f"first, {sum(only_b.values())} only in the second")
        for mark, lines in (("<", only_a), (">", only_b)) if show else ():
            for line, n in sorted(lines.items()):
                print(f"{mark} x{n} {line.strip()[:240]}")
    return int(bool(differing) or ka != kb)


if __name__ == "__main__":
    files = [x for x in sys.argv[1:] if x != "--show"]
    sys.exit(main(*files[:2], show="--show" in sys.argv))
