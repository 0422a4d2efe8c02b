#!/usr/bin/env python
"""Are two trees' compiled programs the same?  (A scope, a comment, a moved
line must not change what the chip runs.)

    cd <tree A> && python <this file> dump /tmp/hlo_a      # no chip needed
    cd <tree B> && python <this file> dump /tmp/hlo_b
    python <this file> compare /tmp/hlo_a /tmp/hlo_b

``dump`` runs the tree's own ``tests/test_chip_compile.py`` train-step and
serving-program tests (ahead-of-time compiles for a described v5e) and
writes every compiled program's HLO text.  ``compare`` strips what names
where an instruction was WRITTEN — ``metadata={op_name=... stack_frame_id=
...}`` and the module's tables of files, functions and stack frames it
points into — and compares the rest.  A Mosaic kernel's body is MLIR
bytecode inside its custom call's ``backend_config`` and embeds the source
locations it was traced under (PERF.md, Findings PR 22): it is parsed and
re-printed WITHOUT debug locations before the comparison.
"""

import base64
import hashlib
import os
import re
import sys

METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                    r"(?:\d+ .*\n)*\n?", re.M)
BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def dump(out: str) -> int:
    os.makedirs(out, exist_ok=True)
    sys.path.insert(0, os.getcwd())
    import pytest
    from jax._src import stages

    compile_ = stages.Lowered.compile
    count = [0]

    def recording(self, *args, **kwargs):
        compiled = compile_(self, *args, **kwargs)
        text = compiled.as_text()
        name = re.search(r"HloModule (\S+?)[, ]", text)
        count[0] += 1
        with open(os.path.join(out, f"{count[0]:02d}_"
                               f"{name.group(1) if name else 'x'}.txt"),
                  "w") as f:
            f.write(text)
        return compiled

    stages.Lowered.compile = recording
    return pytest.main(["tests/test_chip_compile.py", "-q", "-x",
                        "-p", "no:cacheprovider",
                        "-k", "train_step or serving"])


_KERNELS = {}


def kernel_text(body: str) -> str:
    if body not in _KERNELS:
        from jax._src import tpu_custom_call  # noqa: F401  (the dialect)
        from jax._src.lib.mlir import ir

        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            _KERNELS[body] = module.operation.get_asm(
                enable_debug_info=False)
    return _KERNELS[body]


def stripped(text: str):
    kernels = [0]

    def body(match):
        kernels[0] += 1
        digest = hashlib.sha256(kernel_text(match.group(1)).encode())
        return f'"body":"<kernel {digest.hexdigest()[:16]}>"'

    return BODY.sub(body, TABLES.sub("", METADATA.sub("", text))), kernels[0]


def compare(a: str, b: str) -> int:
    same = sorted(os.listdir(a)) == sorted(os.listdir(b))
    for name in sorted(set(os.listdir(a)) & set(os.listdir(b))):
        with open(os.path.join(a, name)) as fa, \
                open(os.path.join(b, name)) as fb:
            (ta, n), (tb, _) = stripped(fa.read()), stripped(fb.read())
        ha, hb = (hashlib.sha256(t.encode()).hexdigest()[:16]
                  for t in (ta, tb))
        print(f"{name:34s} {len(ta):>9d} B {n:>4d} kernels  {ha} | {hb}  "
              f"{'identical' if ta == tb else 'DIFFERENT'}", flush=True)
        if ta != tb:
            same = False
            for la, lb in zip(ta.split("\n"), tb.split("\n")):
                if la != lb:
                    i = next((k for k in range(min(len(la), len(lb)))
                              if la[k] != lb[k]), 0)
                    print("   A:", la[max(0, i - 80):i + 160])
                    print("   B:", lb[max(0, i - 80):i + 160])
                    break
    print("all identical" if same else "DIFFERENCES")
    return 0 if same else 1


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "dump":
        sys.exit(dump(sys.argv[2]))
    if len(sys.argv) == 4 and sys.argv[1] == "compare":
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    sys.exit(__doc__)
