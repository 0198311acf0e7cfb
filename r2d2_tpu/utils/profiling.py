"""Tracing / profiling: ONE facility, one naming table, one clock.

The reference has no profiler at all — its only timing is wall-clock
minutes stored in checkpoints (reference worker.py:378,452) and derived
rates printed every 10 s (worker.py:126,135). Here:

- `span(name, **ids)` annotates a HOST phase. It is a
  `jax.profiler.TraceAnnotation` (free when no profiler session is active,
  and written into the profiler's own trace, so it shares the device
  events' clock by construction; `ids` become the event's stats and tie one
  dispatch's or one batch's spans together) plus an always-on aggregate per
  name (count, total ns, CPU ns of the thread). At close the event is stamped
  `cpu_us`: wall less CPU is the time the thread was not running (a lock, the
  GIL, the kernel, a device queue). Nothing is kept per event. `spanned(name)`
  is the same as a decorator.
- `r2d2.host.gc` is the interpreter's own collections as a span, opened and
  closed from two `gc.callbacks` entries on the thread that triggered the
  collection, so it nests under whatever span is open there. It observes
  only: no threshold, freeze or disable.
- `count(name, n)` counts where the work happens; `counters()` returns the
  counts and the span aggregates as one flat dict. Readers: the benchmark's
  `program_counter` reader and the trainer's metrics row (host ms per dispatch).
- `scoped(fn, name)` is the DEVICE-side scope: `fn` under a named inner
  `jax.jit(..., inline=False)`. The function name is part of the canonical
  IR (`func.func private @r2d2_collect`), hence of the persistent
  compilation cache's key, and of every `op_name` beneath it. A bare
  `jax.named_scope` is metadata only: jax strips it from the cache key, so
  a program with a new scope HITS the entry its scope-less parent wrote and
  runs an executable without the names (PERF.md section 3). XLA inlines the
  call, so instructions and their times do not change.
- `register_program(name, jitted)` wraps a step program: the first call notes
  the abstract signature and its seconds. ONLY WHEN ASKED (the benchmark's
  readers, after the window) it is lowered and compiled from that signature,
  once: `program_scopes(name)` is {instruction: op_name} of that text, and
  `program_heirs(name)` an owner for the instructions that have no op_name.
- `SPANS` is the one table of names; a name outside it is refused.
- `start_trace(dir)` / `stop_trace()` start jax's profiler with the Python
  tracer OFF (it hooks every call of every thread: 8,000 -> 2,500 req/s
  served, PERF.md finding 5). `start_profiler_server(port)` exposes the live
  process to `xprof`/TensorBoard-profile capture.
- `TransferTimer` is the tiered replay plane's staging accountant: how much
  of the host->HBM copy time is hidden behind update compute.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import re
import threading
import time
from typing import Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax

from r2d2_tpu.utils.compilation_cache import compile_seconds

_server = None

# name -> (layer, what it covers). Host spans are `r2d2.<layer>.<phase>`,
# device scopes `r2d2_<region>` (an identifier: it becomes a function name in
# the IR), counters `<layer>.<what>`. PERF.md section 3 prints this table and
# names the metric that reads each entry.
SPANS: Dict[str, tuple] = {
    # host spans
    "r2d2.dispatch": ("dispatch", "one fused dispatch, whole body of runner.step (ids: dispatch, collect)"),
    "r2d2.replay.sample": ("replay", "reserve + draw and, as self time, taking the shard locks (multihost: the draws' upload is inside draw)"),
    "r2d2.replay.reserve": ("replay", "the _reserve_advance call(s) of a collecting dispatch (ids: slots)"),
    "r2d2.replay.draw": ("replay", "the K coordinate draws of one dispatch with their IS-weight arithmetic (ids: k)"),
    "r2d2.dispatch.launch": ("dispatch", "upload + call and, as self time, the async readback kick-off and installing the new stores"),
    "r2d2.dispatch.upload": ("dispatch", "stacking b/s/w and making them and the slot start(s) device arrays (multihost: the slot starts only)"),
    "r2d2.dispatch.call": ("dispatch", "the jitted call alone: argument handling, placement of uncommitted arguments, the enqueue (ids: program)"),
    "r2d2.dispatch.readback": ("dispatch", "np.asarray of the previous dispatch's priorities / chunk bookkeeping: the host waits for the device here"),
    "r2d2.replay.account": ("replay", "installing a drained chunk's blocks into the tree(s)"),
    "r2d2.replay.priorities": ("replay", "the K priority rows of the previous dispatch applied to the tree(s) (ids: offered, applied = the two counters below as the span opens)"),
    "r2d2.replay.stage": ("replay", "tiered plane: one staged K-batch chunk handed to the learner"),
    "r2d2.setup.init": ("CLIs", "Trainer.__init__: env, mesh, model init, replay allocation, checkpoint restore"),
    "r2d2.setup.ring_fill": ("CLIs", "Trainer.warmup: collection until sampling opens"),
    "r2d2.serve.stage": ("serve", "stage + dispatch of one batch on the serve thread (ids: batch, rows, queue_wait_us)"),
    "r2d2.serve.complete": ("serve", "materialize q/action, resolve futures, retire the batch (ids: batch)"),
    "r2d2.host.gc": ("dispatch", "one collection of the interpreter's garbage collector, on the thread that triggered it (ids: generation; collected at close)"),
    # step-correlated spans of the run loops (StepTraceAnnotation)
    "r2d2.step.update": ("run loop", "one learner update of the threaded/inline planes"),
    "r2d2.step.megastep": ("run loop", "one fused dispatch as Trainer.run_fused issues it"),
    # device scopes
    "r2d2_update": ("dispatch", "the K-update scan (learner.make_multi_update_core)"),
    "r2d2_collect": ("dispatch", "the collection chunk: policy, env dynamics, block packing"),
    "r2d2_slab_write": ("replay", "dynamic_update_slice of the chunk's blocks into the stores"),
    "r2d2_gather": ("replay", "in-jit window gather of one batch from the stores"),
    "r2d2_loss": ("model", "the fp32 island: double-Q target, rescale, TD, priorities, loss"),
    "r2d2_optimizer": ("model", "gradient psum, optimizer update, apply, target sync"),
    # counters
    "setup.first_call_s": ("CLIs", "seconds of the first call of each step program (trace, lower, compile or cache load), summed"),
    "setup.compile_s": ("CLIs", "jax's compile-duration total (trace + lower + backend) when the last step program's first call returned"),
    "replay.priority_rows_offered": ("replay", "priority rows handed to ReplayControlPlane.update_priorities"),
    "replay.priority_rows_applied": ("replay", "of those, rows the staleness mask let through to the tree"),
    # what a core's expert mixtures counted in the last update of the last drained dispatch (models/hybrid_stack.py),
    # set (`put`) as that dispatch's priorities are drained: readings, not sums
    "moe.rows_offered": ("model", "token assignments routed to the experts held here, summed over the core's mixtures"),
    "moe.rows_dropped": ("model", "of those, assignments beyond an expert's static capacity: left out and counted"),
    "moe.dropped_share": ("model", "rows_dropped over rows_offered, percent"),
    "moe.load_max_over_mean": ("model", "the fullest of ALL routed experts over the mean one, each summed over the mixtures"),
}

# name -> [count, total ns, cpu ns]; plain counts beside them. Single-writer
# per name on every hot path (the dispatch loop; one serve thread per span
# name; counts under the replay lock; the collector does not re-enter), so no
# lock of their own.
_agg: Dict[str, list] = {}
_counts: Dict[str, float] = {}


def _known(name: str) -> str:
    if name not in SPANS:
        raise KeyError(f"{name!r} is not in profiling.SPANS: add it to the table first")
    return name


class TransferTimer:
    """Host->device staging overlap accountant (tiered replay plane).

    Two accumulators, fed from different threads:
    - `h2d(nbytes)` spans wrap the STAGING side of a chunk — host window
      gather + device_put + transfer completion — measured on the staging
      thread, off the critical path.
    - `wait()` spans wrap the CONSUMER side — the time the update loop
      actually stalled waiting for a staged chunk to be ready.

    overlap_fraction = 1 - wait/h2d, clamped to [0, 1]: 1.0 means every
    byte of copy time was hidden behind compute (the consumer never
    waited), 0.0 means staging was fully serialized ahead of the updates
    (the inline host plane's behavior). Thread-safe; `reset()` rebases the
    window so a bench can exclude compile/warmup chunks."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.h2d_seconds = 0.0
            self.wait_seconds = 0.0
            self.bytes_staged = 0
            self.chunks = 0

    @contextlib.contextmanager
    def h2d(self, nbytes: int = 0) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.h2d_seconds += dt
                self.bytes_staged += nbytes
                self.chunks += 1

    @contextlib.contextmanager
    def wait(self) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.wait_seconds += dt

    def overlap_fraction(self) -> float:
        with self._lock:
            if self.h2d_seconds <= 0.0:
                return 1.0
            return max(0.0, min(1.0, 1.0 - self.wait_seconds / self.h2d_seconds))

    def stats(self) -> dict:
        """One flat dict for metrics/bench JSON."""
        with self._lock:
            h2d, wait = self.h2d_seconds, self.wait_seconds
            chunks, staged = self.chunks, self.bytes_staged
        frac = 1.0 if h2d <= 0.0 else max(0.0, min(1.0, 1.0 - wait / h2d))
        return {
            "h2d_overlap_fraction": round(frac, 4),
            "h2d_seconds": round(h2d, 4),
            "h2d_wait_seconds": round(wait, 4),
            "h2d_chunks": chunks,
            "h2d_gbytes_staged": round(staged / 1e9, 3),
        }


def start_profiler_server(port: int = 9012) -> None:
    """Idempotent: starts the jax.profiler server once per process."""
    global _server
    if _server is None:
        _server = jax.profiler.start_server(port)


def start_trace(log_dir: str) -> None:
    """Start jax's profiler into `log_dir` with the Python tracer off; the
    `TraceAnnotation` spans and the device planes stay. `stop_trace()` ends it."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


stop_trace = jax.profiler.stop_trace


class span:
    """Named host span: a TraceAnnotation (ids -> the event's stats; `cpu_us`,
    the thread's CPU time inside it, stamped at close) plus the always-on
    aggregate of its name."""

    __slots__ = ("_name", "_ann", "_t0", "_cpu0")

    def __init__(self, name: str, **ids):
        self._name = name
        self._ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc):
        # read inside the wall interval, so cpu <= wall on a fine clock; where
        # the clock ticks (gVisor: 10 ms) a span reads whole ticks or none,
        # and only a sum over many spans is a time
        cpu = time.thread_time_ns() - self._cpu0
        dt = time.perf_counter_ns() - self._t0
        self._ann.set_metadata(cpu_us=cpu / 1e3)
        self._ann.__exit__(*exc)
        a = _agg.get(self._name)
        if a is None:
            a = _agg[_known(self._name)] = [0, 0, 0]
        a[0] += 1
        a[1] += dt
        a[2] += cpu
        return False


def spanned(name: str) -> Callable:
    """Decorator: every call of the function runs under `span(name)`; the
    function keeps its signature."""
    _known(name)

    def decorate(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def under_span(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return under_span

    return decorate


def step_span(name: str, step: int):
    """Step-correlated span: groups device work under learner step N."""
    return jax.profiler.StepTraceAnnotation(_known(name), step_num=step)


def count(name: str, n: float = 1) -> None:
    """Add `n` to the counter `name` (a name of the table)."""
    try:
        _counts[name] += n
    except KeyError:
        _counts[_known(name)] = n


def put(name: str, value: float) -> None:
    """Set the counter `name` (a name of the table) to `value`: a reading
    that stands for itself, not a sum."""
    _counts[_known(name)] = float(value)


def counted(name: str) -> float:
    """The counter `name` so far."""
    return _counts.get(name, 0)


def counters() -> Dict[str, float]:
    """Every count, and `<span>.count|.total_ns|.cpu_ns` of every span that
    has run in this process, as one flat dict."""
    out: Dict[str, float] = dict(_counts)
    for name, (n, total, cpu) in list(_agg.items()):
        out[name + ".count"] = n
        out[name + ".total_ns"] = total
        out[name + ".cpu_ns"] = cpu
    return out


# ------------------------------------------------ the interpreter's own pauses

_gc_open: list = []  # the collection in progress: the collector does not re-enter


def _gc_span_start(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("r2d2.host.gc", generation=info["generation"])
        s.__enter__()
        _gc_open.append(s)


def _gc_span_stop(phase: str, info: dict) -> None:
    if phase == "stop" and _gc_open:
        s = _gc_open.pop()
        s._ann.set_metadata(collected=info["collected"])
        s.__exit__(None, None, None)


def _install_gc_span() -> None:
    """Once per process, however often this module is loaded: the start
    handler first in `gc.callbacks` and the stop handler last, so that every
    other callback (jax's own among them) runs inside the span."""
    if not any(getattr(cb, "__module__", None) == __name__ for cb in gc.callbacks):
        gc.callbacks.insert(0, _gc_span_start)
        gc.callbacks.append(_gc_span_stop)


_install_gc_span()


# ------------------------------------------------------------ device scopes


def scoped(fn: Callable, name: str) -> Callable:
    """`fn` as a named, non-inlined inner jit: the name reaches the IR (so
    the compilation cache's key) and the `op_name` of everything beneath it."""

    def inner(*args, **kwargs):
        return fn(*args, **kwargs)

    inner.__name__ = inner.__qualname__ = _known(name)
    return jax.jit(inner, inline=False)


class _Program:
    """A registered step program: calls through to the jitted function, and
    at the first call notes the abstract signature and the call's seconds."""

    def __init__(self, name: str, jitted):
        self.name = name
        self.jitted = jitted
        self.signature = None

    def __call__(self, *args):
        if self.signature is not None:
            return self.jitted(*args)
        self.signature = jax.tree.map(_abstract, args)
        t0 = time.perf_counter()
        out = self.jitted(*args)
        count("setup.first_call_s", time.perf_counter() - t0)
        _counts["setup.compile_s"] = compile_seconds()
        return out

    # the executable's text once a reader has asked (`program_text`). Down here, and no line of `__init__`: a line
    # added above would move `__call__`, a frame of the first call's trace like `scoped`'s `inner` (finding 51.1)
    text: Optional[str] = None


def _abstract(x):
    """ShapeDtypeStruct of one argument; the sharding only where the array is
    committed to it (an uncommitted array follows the others, as at the call)."""
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        return x
    sharding = getattr(x, "sharding", None) if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


_programs: Dict[str, _Program] = {}
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"')


def register_program(name: str, jitted) -> _Program:
    """Register a jitted step program under `name` (latest wins) and return
    the callable to use in its place."""
    prog = _programs[name] = _Program(name, jitted)
    return prog


def registered_programs() -> list:
    """Names of the registered programs that have been called."""
    return [n for n, p in _programs.items() if p.signature is not None]


def program_text(name: str) -> str:
    """The text of the executable of program `name`, lowered and compiled from
    the signature of its first call and kept on the program after the first
    ask (the fifth cell's `mega` is 90 MB: `program_scopes` and
    `program_heirs` read ONE text). Costs a trace, a lowering and, where the
    run's executable is no longer at hand, a cache load or a compile: for a
    reader after the measured window, never for the program itself."""
    prog = _programs[name]
    if prog.signature is None:
        raise ValueError(f"program {name!r} has not been called yet")
    if prog.text is None:
        prog.text = prog.jitted.lower(*prog.signature).compile().as_text()
    return prog.text


def program_scopes(name: str) -> Dict[str, str]:
    """{HLO instruction name: op_name} of the executable of program `name`."""
    return parse_op_names(program_text(name))


def program_heirs(name: str) -> Dict[str, Tuple[str, str]]:
    """{HLO instruction name: (op_name, how)} for the instructions of the
    executable of program `name` that have NO op_name of their own: who owns
    their device time, by the rules of `parse_heirs`."""
    return parse_heirs(program_text(name))


def parse_op_names(hlo_text: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_BRACKET = re.compile(r"[()]")
_OPERAND = re.compile(r"%?([\w.\-]+)\s*(?:,|$)")
_CALLED = {"fusion": re.compile(r"\bcalls=%?([\w.\-]+)"), "while": re.compile(r"\bbody=%?([\w.\-]+)")}
_INDEX = re.compile(r"\bindex=(\d+)")

WAITS_FOR, FUSED, FEEDS = "waits_for", "fused", "feeds"
PLUMBING = ("while", "tuple", "get-tuple-element", "parameter")  # they hand values on: named or not, a walk goes through


class Instruction(NamedTuple):
    name: str
    opcode: str
    shape: str            # the result's, with its layout: `bf16[516,128]{1,0:T(8,128)(2,1)S(1)}`, or a tuple of such
    operands: List[str]   # the words of the operand list that may be names (the caller keeps those it knows)
    root: bool
    calls: Optional[str]  # the computation a `fusion` calls, the body of a `while`
    index: int            # of a `get-tuple-element`, else -1


def _closing(line: str, start: int) -> int:
    """Index just past the bracket that closes the `(` at `line[start]`."""
    depth = 0
    for m in _BRACKET.finditer(line, start):
        depth += 1 if m.group() == "(" else -1
        if depth == 0:
            return m.end()
    return len(line)


def parse_instructions(hlo_text: str) -> Dict[str, List[Instruction]]:
    """{computation: its instructions in text order} of a compiled module's
    text. In a scheduled module (`is_scheduled=true`, what a TPU executable
    prints) text order is schedule order."""
    out: Dict[str, List[Instruction]] = {}
    body: Optional[List[Instruction]] = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                body = out[c.group(1)] = []
            continue
        if body is None:
            continue
        # the result's shape: a tuple in brackets (with tiles `T(8,128)` inside), else one word
        shape_end = _closing(line, m.end()) if line.startswith("(", m.end()) else line.find(" ", m.end())
        op = _OPCODE.match(line, shape_end) if shape_end >= 0 else None
        if op is None:
            continue
        opcode = op.group(1)
        end = _closing(line, op.end() - 1)
        called = _CALLED[opcode].search(line, end) if opcode in _CALLED else None
        index = _INDEX.search(line, end) if opcode == "get-tuple-element" else None
        body.append(Instruction(m.group(2), opcode, line[m.end():shape_end], _OPERAND.findall(line, op.end(), end - 1),
                                bool(m.group(1)), called.group(1) if called else None,
                                int(index.group(1)) if index else -1))
    return out


def top_level(comps: Dict[str, List[Instruction]]) -> Iterator[Instruction]:
    """The instructions outside the fusions' bodies: what the device runs as
    events of its own, and what a program writes to memory."""
    fused = {i.calls for body in comps.values() for i in body if i.opcode == "fusion"}
    for comp, body in comps.items():
        if comp not in fused:
            yield from body


def parse_heirs(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction: (op_name, how)} for the instructions of a compiled
    module's text that carry NO op_name, each from a named instruction beside
    it. Three rules, by what the unnamed instruction is:

    - `how = "fused"`: a `fusion`. The op_name of the ROOT of the computation
      it calls, else of the root's nearest named producer inside that
      computation (a mask packer's root is an unnamed `reduce` over the
      model's own `gt`). A fusion whose computation names nothing falls to
      the third rule.
    - `how = "waits_for"`: the `-done` of an asynchronous pair (`copy-done`,
      `slice-done`, `all-gather-done`, ...), what memory-space assignment puts
      in for a prefetch: the name of its nearest consumer. The wait belongs to
      whoever needed the operand.
    - `how = "feeds"`: anything else (`copy`, `convert`, `bitcast`, a
      `-start`, ...): the name of its nearest consumer, else of its nearest
      producer. A `-done` that nobody consumes but its loop's carry (a write
      back) takes its producer's name this way too.

    "The name" of a neighbour is its own op_name or, for an unnamed fusion,
    what the first rule gave it; a neighbour that has neither is walked
    THROUGH (an unnamed `bitcast` or `ConcatBitcast` in between), and so is
    every `tuple`, `get-tuple-element`, `parameter` and `while`, named or not
    (`PLUMBING`): they hand values on, the elements of a scan's result all
    carry the scan's name and an argument its path. "Nearest" is fewest steps,
    then schedule order for consumers and operand order for producers. A
    `while` is walked through element by element: what feeds element k of its
    operand tuple is consumed by the body's `get-tuple-element` k, and element
    k of its result is produced by operand k of the body's root (a weight
    prefetched before the scan waits for the layer that reads it inside). A
    name is taken as it is, whether a bucket wants it or not. An instruction
    that none of this names is absent from the map, and instructions inside
    fused computations, which are no device events, get no entry."""
    named = parse_op_names(hlo_text)
    comps = parse_instructions(hlo_text)

    def nearest(start: str, step: Dict[str, List[str]], name_of: Dict[str, str]) -> Optional[str]:
        seen, level = {start}, [start]
        while level:
            following = []
            for at in level:
                for nxt in step.get(at, ()):
                    if nxt in name_of:
                        return name_of[nxt]
                    if nxt not in seen:
                        seen.add(nxt)
                        following.append(nxt)
            level = following
        return None

    # who produces and who consumes what, inside each computation
    producers: Dict[str, List[str]] = {}
    consumers: Dict[str, List[str]] = {}
    by_name: Dict[str, Instruction] = {}
    for body in comps.values():
        here = {i.name for i in body}
        by_name.update((i.name, i) for i in body)
        for i in body:
            producers[i.name] = [o for o in dict.fromkeys(i.operands) if o in here]
            if i.opcode != "while":
                for o in producers[i.name]:
                    consumers.setdefault(o, []).append(i.name)
    for body in comps.values():
        for w in (i for i in body if i.opcode == "while"):
            inside = comps.get(w.calls or "", [])
            operand = by_name.get(w.operands[0]) if w.operands else None
            root = next((j for j in inside if j.root), None)
            if root is None or operand is None or operand.opcode != "tuple":
                continue
            parameter = next((j.name for j in inside if j.opcode == "parameter"), None)
            for j in inside:  # into the body: element k of the operand tuple is the body's get-tuple-element k
                if j.opcode == "get-tuple-element" and j.operands[:1] == [parameter] and j.index < len(operand.operands):
                    consumers.setdefault(operand.operands[j.index], []).append(j.name)
                    producers[j.name] = [operand.operands[j.index]]
            for j in body:    # out of it: element k of the result is operand k of the body's root
                if j.opcode == "get-tuple-element" and j.operands[:1] == [w.name] and j.index < len(root.operands):
                    consumers.setdefault(root.operands[j.index], []).append(j.name)
                    producers[j.name] = [root.operands[j.index]]

    heirs: Dict[str, Tuple[str, str]] = {}
    for f in (i for body in comps.values() for i in body if i.opcode == "fusion" and i.name not in named):
        root = next((j.name for j in comps.get(f.calls or "", []) if j.root), None)
        got = root and (named.get(root) or nearest(root, producers, named))
        if got:
            heirs[f.name] = (got, FUSED)
    name_of = {k: v for k, v in named.items() if k in by_name and by_name[k].opcode not in PLUMBING}
    name_of.update({k: v[0] for k, v in heirs.items()})
    for i in top_level(comps):
        if i.name in named or i.name in heirs:
            continue
        got = nearest(i.name, consumers, name_of)
        if got:
            heirs[i.name] = (got, WAITS_FOR if i.opcode.endswith("-done") else FEEDS)
        else:
            got = nearest(i.name, producers, name_of)
            if got:
                heirs[i.name] = (got, FEEDS)
    return heirs


_RELAYOUT = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+ = ([a-z]+\d*)\[([\d,]*)\]\S* (?:copy|transpose|reshape)\([^)]*\))")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}


def relayouts_at_least(hlo_text: str, min_bytes: int) -> list:
    """Every `copy` / `transpose` / `reshape` instruction of a compiled
    module, inside a fusion or out, whose result holds at least `min_bytes`: a
    pass over that much memory that only re-lays data out (a reshape that
    moves nothing is a `bitcast` by then). What the whole-store copy of
    PERF.md finding 1 was; the rehearsal and tests/test_v5e_compile.py ask
    with half the obs store's bytes and want none."""
    out = []
    for line in hlo_text.splitlines():
        m = _RELAYOUT.match(line)
        if m and m.group(2) in _DTYPE_BYTES:
            size = _DTYPE_BYTES[m.group(2)]
            for d in filter(None, m.group(3).split(",")):
                size *= int(d)
            if size >= min_bytes:
                out.append(m.group(1))
    return out
