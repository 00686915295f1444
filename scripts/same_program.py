"""Whether two compiled texts are one program, source locations apart.

    python3 scripts/same_program.py PARENT.txt CHANGE.txt      (or .txt.gz)

The texts are what ``fedbench/scope_split.py --hlo-of <cell> --seed n
--out <file>`` writes on the chip, once from the parent's tree and once
from the change's. A refactor that claims "the same compiled program"
shows it with this (PR 44). Compared are all lines but the tables of
file and function names at the head of a text, less each instruction's
``source_file`` / ``source_line`` / ``stack_frame_id``. A Pallas kernel's
``tpu_custom_call`` carries its body serialized (MLIR bytecode, base64)
with the Python positions it was traced at inside: where two lines
differ in that body alone, the two bodies are printed without their
debug locations and compared as text. Exit code 0: the same program.
"""

import base64
import gzip
import re
import sys

LOCATION = re.compile(
    r' ?(source_file="[^"]*"|source_line=\d+|source_end_line=\d+'
    r'|source_column=\d+|source_end_column=\d+|stack_frame_id=\d+)')
TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\b")
BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def stripped(path: str) -> list:
    """The text's lines without the name tables and the locations."""
    opened = gzip.open(path, "rt") if path.endswith(".gz") else open(path)
    out, in_table = [], False
    with opened as text:
        for line in text:
            if TABLE.match(line):
                in_table = True
                continue
            if in_table:
                if line.strip() == "" or re.match(r"^\d+ ", line):
                    continue
                in_table = False
            out.append(LOCATION.sub("", line.rstrip("\n")))
    return out


def kernel_text(body64: str) -> str:
    """A serialized kernel body as MLIR text without debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # ``stable_mosaic``
    with ctx:
        module = ir.Module.parse(base64.b64decode(body64))
        return module.operation.get_asm(enable_debug_info=False)


def main(parent: str, change: str) -> int:
    x, y = stripped(parent), stripped(change)
    differing = [i for i, (p, q) in enumerate(zip(x, y)) if p != q]
    outside = [i for i in differing
               if BODY.sub('"body":""', x[i]) != BODY.sub('"body":""', y[i])]
    bodies = [i for i in differing if i not in set(outside)
              and kernel_text(BODY.search(x[i]).group(1))
              != kernel_text(BODY.search(y[i]).group(1))]
    same = len(x) == len(y) and not outside and not bodies
    print(f"{'SAME' if same else 'DIFFERENT'} {parent} {change}: {len(x)} / "
          f"{len(y)} lines; {len(differing)} differ, {len(outside)} of them "
          f"outside a kernel's serialized body, {len(bodies)} in a body "
          f"printed without debug locations", flush=True)
    for i in (outside + bodies)[:5]:
        print(f"  line {i}:\n   - {x[i][:400]}\n   + {y[i][:400]}")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
