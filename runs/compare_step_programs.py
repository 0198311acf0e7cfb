"""Are two trees' step programs the same programs? Compares what
`runs/rehearse_step_programs.py --hlo-dir` wrote for each tree (the compiled
texts for the described v5e, and the JSON lines it printed), with everything
that only says WHERE in the source an instruction came from taken out:

    JAX_PLATFORMS=cpu python runs/rehearse_step_programs.py nature-lstm512 --hlo-dir A/nature-lstm512 > A/nature-lstm512.jsonl   # on the parent
    JAX_PLATFORMS=cpu python runs/rehearse_step_programs.py nature-lstm512 --hlo-dir B/nature-lstm512 > B/nature-lstm512.jsonl   # on the change
    JAX_PLATFORMS=cpu python runs/compare_step_programs.py A B

A PR that moves code without meaning to change a cell's program (PR 51: five
step builders deleted above and below the ones the cells run) proves it this
way, without the chip. Three things carry source positions and nothing else:
`metadata={...}` on every instruction, the module's four tables at its head
(FileNames, FunctionNames, FileLocations, StackFrames), and the debug
locations inside each Mosaic kernel's serialized MLIR (`backend_config`'s
`body`: file paths and line numbers of the whole call stack, so it changes
whenever `learner.py` gains or loses a line above the core). The kernels are
compared as their MLIR printed without locations. Exit 0: every program equal;
1: a difference is printed.

A step-0 aid, not a gate: reading the kernels' MLIR takes the private
`jax._src.lib.mlir`, which a jax bump may move."""

from __future__ import annotations

import base64
import difflib
import glob
import json
import os
import re
import sys

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _kernel_without_locations(match) -> str:
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    module = ir.Module.parse(base64.b64decode(match.group(1)), ctx)
    return '"body":' + json.dumps(module.operation.get_asm(enable_debug_info=False))


def without_source_positions(text: str) -> str:
    """A compiled program's text less its source positions (module docstring)."""
    out, in_table = [], False
    for line in text.splitlines():
        if line in _TABLES:
            in_table = True
        elif in_table:
            in_table = bool(line.strip())
        else:
            line = _METADATA.sub("", line)
            if "tpu_custom_call" in line:
                line = _BODY.sub(_kernel_without_locations, line)
            out.append(line)
    return "\n".join(out)


def compare(parent: str, change: str) -> int:
    differing = 0
    for row_file in sorted(glob.glob(os.path.join(parent, "*.jsonl"))):
        config = os.path.basename(row_file)[: -len(".jsonl")]
        rows = [[json.loads(l) for l in open(os.path.join(d, config + ".jsonl"))] for d in (parent, change)]
        for a, b in zip(*rows):
            texts = [
                without_source_positions(open(os.path.join(d, config, f"{config}.{a['program']}.hlo")).read())
                for d in (parent, change)
            ]
            fields = sorted(k for k in a if k != "compile_s" and a[k] != b.get(k))
            same = texts[0] == texts[1] and not fields
            differing += not same
            print(json.dumps({
                "config": config, "program": a["program"], "module": [a["module"], b["module"]],
                "instructions": [a["instructions"], b["instructions"]], "text_equal": texts[0] == texts[1],
                "lines": len(texts[0].splitlines()), "row_fields_differing": fields,
                "temp_gb": [a["temp_gb"], b["temp_gb"]], "argument_gb": [a["argument_gb"], b["argument_gb"]],
                "alias_gb": [a["alias_gb"], b["alias_gb"]],
            }), flush=True)
            if texts[0] != texts[1]:
                for l in list(difflib.unified_diff(*(t.splitlines() for t in texts), lineterm="", n=0))[:20]:
                    print("   ", l[:240])
    return 1 if differing else 0


if __name__ == "__main__":
    sys.exit(compare(sys.argv[1], sys.argv[2]))
