"""Reader `trace_heirs` (source `device_trace`): device time by OWNER. What
`trace_scope` sorts by an instruction's own op_name and calls `unscoped` where
there is none, this reader sorts by the op_name or, for an instruction without
one, by the heir the compiled text gives it
(r2d2_tpu/utils/profiling.program_heirs: the consumer a `copy-done` waits
for, the root of an unnamed fusion's computation, the consumer or producer of
an unnamed `copy` or `convert`). The sorting itself is trace_scope's
(`attribute`: the program of an execution, the ordered buckets of
benchmark/trace_scopes.json, self time, the mean over devices), called with
each program's two maps laid over each other, so the identity carries over:
the seven owned buckets + unowned = the device's busy time. `unowned` is what
neither kind of name puts in a bucket: an op_name or an heir that no bucket
wants (the scan's own slicing under `jit(r2d2_update)/while`), an instruction
that no rule names, an event outside every execution of a registered program.

spec: {"bucket": <name>, "per": "updates", "scale": 1000.0}   the bucket + what it inherits, per update
  or  {"share": "unowned"}                                    % of busy time that has no owner
  or  {"share": "prefetch_wait"}                              % of busy time that is the self time of
      asynchronous `*-done` instructions, whoever owns them: what the step spends
      waiting for the fast memory and for weight slices (a CPU trace has none: 0.0, said so)

Nothing to read (None): no trace, or a program without `program_heirs` (a
parent commit). Every member with its heir and how it got it goes to
<work dir>/scopes/<cell>.heirs.json, beside trace_scope's own file."""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, Optional

from benchmark import trace as tr
from benchmark.readers import scaled, trace_scope

UNOWNED = trace_scope.UNSCOPED  # the key `attribute` gives what no bucket takes
OWN = "own"  # how an instruction with an op_name of its own got its owner
_SEP = "\t"  # an heir's op_name goes through `attribute` as "<op_name>\t<how>": no bucket's regex spans it
_DONE = re.compile(r"^%?[\w.\-]*-done\b")
_done: Dict[int, dict] = {}  # id(trace) -> attribution of that trace


def program_maps() -> Optional[Dict[str, Dict[str, str]]]:
    """{registered step program: {instruction: op_name, or "<heir's op_name>\\t<how>"}};
    None without the facility."""
    try:
        from r2d2_tpu.utils import profiling

        names, heirs_of = profiling.registered_programs(), profiling.program_heirs
    except (ImportError, AttributeError):
        return None
    maps = {}
    for name in names:
        t = time.perf_counter()
        own = profiling.program_scopes(name)
        t_own = time.perf_counter() - t
        heirs = heirs_of(name)
        hows: Dict[str, int] = {}
        for _, how in heirs.values():
            hows[how] = hows.get(how, 0) + 1
        print(f"[bench] heirs of step program {name!r}: {len(heirs)} instructions without op_name get an owner "
              f"({', '.join(f'{k} {v}' for k, v in sorted(hows.items()))}) in {time.perf_counter() - t - t_own:.2f}s, "
              f"beside {len(own)} named in {t_own:.2f}s", flush=True)
        maps[name] = {**own, **{i: op + _SEP + how for i, (op, how) in heirs.items()}}
    return maps


def owned(got: dict) -> dict:
    """What `attribute` returned for the laid-over maps, read by owner:
    {"seconds": {bucket: s} with UNOWNED last, "busy": s, "wait": s of `*-done`
    self time, "by_how": {bucket: {own | waits_for | fused | feeds: s}},
    "rows": [(bucket, event text, op_name, how, s), ...] largest first}."""
    rows, by_how, wait = [], {b: {} for b in got["seconds"]}, 0.0
    for b, text, op, v in got["rows"]:
        op, _, how = op.partition(_SEP)
        how = how or OWN
        rows.append((b, text, op, how, v))
        by_how[b][how] = by_how[b].get(how, 0.0) + v
        if _DONE.match(text):
            wait += v
    return {"seconds": got["seconds"], "busy": got["busy"], "wait": wait, "by_how": by_how, "rows": rows}


def _write_members(ctx, got: dict) -> None:
    rows = [{"bucket": b, "instruction": tr.op_label(text.split("|")[0]), "how": how, "seconds": v,
             **({"op_name": op} if how == OWN else {"heir": op})}
            for b, text, op, how, v in got["rows"]]
    path = os.path.join(ctx.work_dir("scopes"), ctx.cell.name + ".heirs.json")
    with open(path, "w") as fh:
        json.dump({"seconds": got["seconds"], "busy": got["busy"], "prefetch_wait": got["wait"],
                   "by_how": got["by_how"], "rows": rows}, fh)
    print(f"[bench] every bucket's members by owner: {path}", flush=True)


def attribution(ctx) -> Optional[dict]:
    if ctx.trace_data is None or not ctx.trace_data.ops:
        return None
    key = id(ctx.trace_data)
    if key not in _done:
        maps = program_maps()
        if maps is None:
            return None
        _done.clear()
        got = _done[key] = owned(trace_scope.attribute(ctx.trace_data, maps, trace_scope.load_scopes(ctx.cell.bench_dir)))
        busy = got["busy"] or 1.0
        print("[bench] device time by owner, own + inherited: " + ", ".join(
            f"{b} {got['by_how'][b].get(OWN, 0.0):.4f}s"
            + "".join(f" + {how} {v:.4f}s" for how, v in sorted(got["by_how"][b].items()) if how != OWN)
            + f" ({100 * s / busy:.2f}%)" for b, s in got["seconds"].items())
            + f"; sum {got['busy']:.4f}s; `*-done` waits {got['wait']:.4f}s ({100 * got['wait'] / busy:.2f}%)", flush=True)
        if got["wait"] <= 0.0:
            print("[bench] no asynchronous `*-done` event in the trace (a CPU's has none): "
                  "the prefetch waits read 0", flush=True)
        _write_members(ctx, got)
        left = [(tr.op_label(text.split("|")[0]), op, how, v) for b, text, op, how, v in got["rows"] if b == UNOWNED]
        if left:
            print("[bench] largest unowned: " + "; ".join(
                f"{label} [{trace_scope._tail(op)}{'' if how == OWN else ', ' + how}] {v:.4f}s"
                for label, op, how, v in left[:trace_scope.TOP]), flush=True)
    return _done[key]


def read(spec, ctx):
    got = attribution(ctx)
    if got is None:
        return None
    if "share" in spec:
        seconds = {"unowned": got["seconds"][UNOWNED], "prefetch_wait": got["wait"]}[spec["share"]]
        return 100.0 * seconds / got["busy"] if got["busy"] > 0 else 0.0
    if spec["bucket"] not in got["seconds"] or spec["bucket"] == UNOWNED:
        raise KeyError(f"{spec.get('name', '?')}: no bucket {spec['bucket']!r} "
                       f"(have {sorted(set(got['seconds']) - {UNOWNED})})")
    return scaled(spec, ctx, got["seconds"][spec["bucket"]])
