#!/usr/bin/env python3
"""Are two optimized HLO texts of a whole step the same program?

    JAX_PLATFORMS=cpu python3 tools/compare_step_hlo.py parent.hlo change.hlo

The texts are what `chipbench/compile_check_large.py --hlo <file>` keeps.
Two checkouts of an equal program still differ in their source locations:
the metadata's files and lines, and the debug locations inside each Mosaic
kernel's serialised body.  So the text is compared line for line with the
metadata's locations and the kernels' bodies cut out, and the kernels are
compared as MLIR printed without debug information.  **That comparison
alone is the verdict and the exit code** (0: equal): it is the only one
that cannot call two different programs the same.

Where the verdict is "different", two reports say what differs; neither
moves the exit code, and each is blind to something the other sees:

- the lines as a multiset with the instructions' numbers stripped
  (`%fusion.123` -> `%fusion`), printed with ``--show``.  A change that
  gives a kernel one more operand renumbers every instruction after it;
  what is left here is the lines the change touched, operands and layouts
  included.
- the multiset of (opcode, result type, scope) over every instruction that
  is no copy, bitcast, slice or tuple plumbing, layouts and operands left
  out.  A change that takes instructions AWAY moves more than numbers: the
  compiler assigns memory spaces and prefetches anew (`S(1)`,
  `copy-start`, `slice-done`), and every fused computation's header lists
  its renumbered parameters, so the first report runs to a thousand lines.
  What is left here is the work that came or went; an operand or a layout
  that changed is not seen.
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
# `%fusion.123`, and a fused computation's `%param_2.7814`: the number after
# the dot moves with every instruction that comes or goes before it
_NUMBER = re.compile(r"(%[A-Za-z_\-]+(?:_\d+)?)(?:\.\d+)+")


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


_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PLUMBING = frozenset((
    "parameter", "bitcast", "get-tuple-element", "tuple", "constant", "copy",
    "copy-start", "copy-done", "slice-start", "slice-done"))


def computed(lines):
    """The lines' instructions as a multiset of (opcode, result type
    without layout, op_name): what the program computes, whatever the
    compiler named, placed or prefetched."""
    out = collections.Counter()
    for line in lines:
        m = _INSTRUCTION.match(line)
        if not m or m.group(2) in _PLUMBING or "ConcatBitcast" in line:
            continue
        scope = _OP_NAME.search(line)
        out[(m.group(2), _LAYOUT.sub("", m.group(1)),
             scope.group(1) if scope else "")] += 1
    return out


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
        ca, cb = computed(la), computed(lb)
        gone, come = ca - cb, cb - ca
        print(f"by opcode, result type and scope: {sum(gone.values())} "
              f"instructions only in the first, {sum(come.values())} only "
              "in the second")
        for mark, ops in (("<", gone), (">", come)):
            for (op, typ, scope), n in sorted(ops.items()):
                print(f"{mark} x{n} {op} {typ[:80]} {scope[-120:]}")
    return int(bool(differing) or ka != kb)


if __name__ == "__main__":
    files = [x for x in sys.argv[1:] if x != "--show"]
    sys.exit(main(*files[:2], show="--show" in sys.argv))
