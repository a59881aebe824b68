"""The serving program's own spans, and the device's time by scope, from
a profiler trace (``.xplane.pb``).

Host spans.  The served path opens ``jax.profiler.TraceAnnotation``
spans at each layer boundary (``docs/serving.md`` lists them):
``serve.tick`` and, inside it, ``serve.ingest``, ``serve.schedule``
(``sched.decide``), ``engine.prefill``, ``engine.dispatch``,
``serve.consume``; ``engine.warm`` at every warm-path entry; and
``serve.sync`` / ``engine.sync`` around each wait on the device.  A
span's arguments (``req``, ``tokens``, ``width``, ``waiting``,
``program``, ``length``) are TraceMe metadata: event stats in the
trace, or a ``name#k=v,...#`` suffix; :func:`load_spans` returns the
bare name and the arguments apart.  Nesting on the host thread gives
each span its parent.

Device scopes.  ``XLA Ops`` events carry the optimized HLO instruction
as their name and nothing of its metadata.  :func:`scope_index` reads
the instructions' ``op_name`` metadata (``jit(f)/attn_proj/dot_general``)
from the optimized HLO text of the executables that ran
(``jitted.lower(...).compile().as_text()``), and :func:`device_scopes`
sums each event's device time under its scope: the ``jax.named_scope``
path without the ``jit(...)`` wrappers and the primitive.  An
instruction without such an ``op_name`` (a layout copy of a parameter,
say) goes under ``<unscoped>``, and its result shape is kept.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace_reduce
from .trace_reduce import Event

PREFIXES = ("serve.", "sched.", "engine.")
UNSCOPED = "<unscoped>"


@dataclasses.dataclass
class Span(Event):
    args: dict = dataclasses.field(default_factory=dict)


def parse_name(raw: str) -> Tuple[str, dict]:
    """``engine.prefill#req=3,tokens=120#`` -> (``engine.prefill``,
    ``{"req": 3, "tokens": 120}``); a bare name has no arguments."""
    name, _, rest = raw.partition("#")
    args = {}
    for kv in rest.rstrip("#").split(","):
        k, eq, v = kv.partition("=")
        if eq:
            args[k] = int(v) if re.fullmatch(r"-?\d+", v) else v
    return name, args


def load_spans(path: str) -> List[Span]:
    """The program's and the harness's (``bench.*``) host spans, in
    start order."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name, args = parse_name(e.name)
                if not name.startswith(PREFIXES + ("bench.",)):
                    continue
                args.update((k, v) for k, v in e.stats)
                out.append(Span(name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns), args))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


# ---------------------------------------------------------- device scopes
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = (.+?) [a-z][\w-]*\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule ([\w.-]+)")
_WRAPPER = re.compile(r"^(?:jit|pjit|xla_pmap|pmap)\(.*\)$")


def instruction(text: str) -> Optional[Tuple[str, str]]:
    """(instruction name, result type) of an HLO instruction's text."""
    m = _INSTR.match(text)
    return (m.group(1), m.group(2)) if m else None


def scope_of(op_name: Optional[str]) -> str:
    """``jit(f)/jit(main)/attn_proj/dot_general`` -> ``attn_proj``: the
    named-scope path of an instruction's ``op_name``; ``<unscoped>``
    where it has none (no metadata, or not a traced op's path)."""
    if not op_name:
        return UNSCOPED
    parts = op_name.split("/")
    if not _WRAPPER.match(parts[0]):
        return UNSCOPED
    path = [p for p in parts[:-1] if not _WRAPPER.match(p)]
    return "/".join(path) or UNSCOPED


def scope_index(hlo_texts: Iterable[str]) -> Dict[tuple, str]:
    """(module, instruction, result type) -> scope, over the optimized
    HLO texts of the executables that ran."""
    index: Dict[tuple, str] = {}
    for text in hlo_texts:
        module = None
        for line in text.splitlines():
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
            ins = instruction(line)
            if ins is None:
                continue
            op = _OP_NAME.search(line)
            index.setdefault((module,) + ins,
                             scope_of(op.group(1) if op else None))
    return index


def device_scopes(ops: Sequence[Event], modules: Sequence[Event],
                  index: Dict[tuple, str], lo: int, hi: int
                  ) -> Tuple[Dict[str, int], Dict[str, int]]:
    """Device nanoseconds in ``[lo, hi)`` by scope, and, of the
    ``<unscoped>`` time, by ``opcode result-type``.  ``ops`` carry the
    raw instruction text as their name; each is matched to the program
    run (``modules``) that holds it.  An op of a program that is not in
    ``index`` goes under ``<program name>``."""
    runs = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in runs]
    indexed = {k[0] for k in index}
    by_scope: Dict[str, int] = defaultdict(int)
    unscoped: Dict[str, int] = defaultdict(int)
    for e in ops:
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        k = bisect.bisect_right(starts, e.start) - 1
        module = trace_reduce.module_name(runs[k].name) \
            if k >= 0 and runs[k].end >= e.end else None
        ins = instruction(e.name)
        if module in indexed:
            scope = index.get((module,) + ins, UNSCOPED) if ins \
                else UNSCOPED
        else:
            scope = f"<{module}>"
        by_scope[scope] += t - s
        if scope == UNSCOPED:
            label = trace_reduce.op_name(e.name)
            if ins:
                label += " " + re.sub(r"\{[^}]*\}", "", ins[1])
            unscoped[label] += t - s
    return dict(by_scope), dict(unscoped)


def load_raw_ops(path: str) -> Tuple[Dict[str, List[Event]],
                                      Dict[str, List[Event]]]:
    """Device ``XLA Ops`` with their raw instruction text as the name,
    and ``XLA Modules``, by device plane."""
    from jax.profiler import ProfileData
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = [Event(e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                   for e in line.events]
            (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
    return ops, modules
