"""Which model op owns each instruction of a compiled program.

Every executor compile (``core/executor.py``) hands its executable to
``record``, which reads the optimized HLO once and keeps, for each
instruction name, an ``Owner``:

* ``owner``: the scope of an IR op, ``l<layer>.<kind>.<output>``
  (``codegen.op_scope``, ``codegen.output_scope``), or ``loss``, or
  ``optimizer``;
* ``direction``: ``backward`` when the owner sits under ``transpose(`` in
  the instruction's ``op_name`` metadata, else ``forward``;
* ``inner``: the scopes and primitive below the owner, ``/``-joined
  (``scatter-add`` in the backward of a row gather,
  ``jit(segment_mm_padded)/segment_mm_padded/pallas_call`` for a kernel).

A fusion takes its root's owner, else the owner most of its fused
instructions carry. Instructions with no owner (copies and layout changes
that XLA inserts) map to None.

Tables are kept per HLO module name (``jit_hector_train_step``), the latest
compile of each, in a bounded process-wide registry that outlives the
program; they hold strings only, never the executable.

Operator use — name a profile's device time after model ops::

    with jax.profiler.trace("/tmp/prof"):
        for _ in range(3):
            state, _ = trainer.step(state)
        jax.block_until_ready(state)
    for owner, s in device_ops.profile_seconds(
            "/tmp/prof", "jit_hector_train_step").items():
        print(owner, s)          # Owner(...) or None (no owner): seconds
"""
from __future__ import annotations

import collections
import glob
import os
import re
import threading
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

MAX_MODULES = 64
OWNERS = re.compile(r"^(l\d+\.[a-z]+\.[^/]+|loss|optimizer)$")

_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"\bcalls=\{?%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAP = re.compile(r"^([\w-]+)\((.*)\)$")


class Owner(NamedTuple):
    owner: str
    direction: str       # "forward" | "backward"
    inner: str


Table = Dict[str, Optional[Owner]]

_TABLES: "collections.OrderedDict[str, Table]" = collections.OrderedDict()
_LOCK = threading.Lock()


def _split(op_name: str) -> List[str]:
    """``a/jvp(b/c)/d`` -> ``[a, jvp(b/c), d]``: ``/`` outside brackets."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


def _unwrap(part: str) -> Tuple[List[str], str]:
    """``transpose(jvp(l0.gemm.hs))`` -> ``(["transpose", "jvp"], ...)``."""
    transforms = []
    m = _WRAP.match(part)
    while m:
        transforms.append(m.group(1))
        part = m.group(2)
        m = _WRAP.match(part)
    return transforms, part


def parse_op_name(op_name: str) -> Optional[Owner]:
    """The owner an ``op_name`` metadata string names, or None."""
    backward = False
    parts = _split(op_name)
    for i, part in enumerate(parts):
        transforms, name = _unwrap(part)
        backward = backward or "transpose" in transforms
        if OWNERS.match(name):
            inner = "/".join(_unwrap(p)[1] for p in parts[i + 1:])
            return Owner(name, "backward" if backward else "forward", inner)
    return None


def parse_hlo(text: str) -> Tuple[str, Table]:
    """``(module name, table)`` of an optimized HLO module's text."""
    module = ""
    comps: Dict[str, List[Tuple[str, Optional[Owner], Optional[str],
                                bool]]] = {}
    cur: Optional[List] = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            name = words[1] if words[0] == "ENTRY" else words[0]
            cur = comps.setdefault(name.lstrip("%"), [])
        elif cur is not None:
            m = _INSTR.match(line)
            if m is None:
                continue
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            cur.append((m.group(2), parse_op_name(op.group(1)) if op else
                        None, calls.group(1) if calls else None,
                        bool(m.group(1))))
    called = {c for instrs in comps.values() for _, _, c, _ in instrs if c}
    memo: Dict[str, Optional[Owner]] = {}

    def of_computation(name: str) -> Optional[Owner]:
        if name not in memo:
            memo[name] = None          # a cycle resolves to no owner
            instrs = comps.get(name, [])
            owners = [resolve(i) for i in instrs]
            root = [o for o, i in zip(owners, instrs) if i[3]]
            found = [o for o in owners if o is not None]
            memo[name] = (root[0] if root and root[0] is not None else
                          collections.Counter(found).most_common(1)[0][0]
                          if found else None)
        return memo[name]

    def resolve(instr) -> Optional[Owner]:
        _, own, calls, _ = instr
        if own is None and calls is not None:
            return of_computation(calls)
        return own

    table: Table = {}
    for comp, instrs in comps.items():
        if comp in called:
            continue
        for instr in instrs:
            table[instr[0]] = resolve(instr)
    return module, table


def record(compiled) -> Optional[str]:
    """Build and keep the table of a compiled executable (``jax.stages
    .Compiled``); returns its module name, or None where the executable
    holds no HLO text."""
    try:
        text = compiled.as_text()
    except Exception:  # a backend may keep no HLO with the executable
        return None
    if not text:
        return None
    module, table = parse_hlo(text)
    with _LOCK:
        _TABLES[module] = table
        _TABLES.move_to_end(module)
        while len(_TABLES) > MAX_MODULES:
            _TABLES.popitem(last=False)
    return module


def table(module: str) -> Optional[Table]:
    with _LOCK:
        return _TABLES.get(module)


def instruction_name(op: str) -> str:
    """A profile's op name (``%fusion.55 = f32[...]``) -> ``fusion.55``."""
    return op.split(" = ")[0].strip().lstrip("%")


def owner(op: str, module: str) -> Optional[Owner]:
    """The owner of one instruction (by name, or a profile's op name) of
    ``module``; None without an owner or a table."""
    t = table(module)
    return None if t is None else t.get(instruction_name(op))


def attribute(op_seconds: Mapping[str, float], module: str
              ) -> Optional[Dict[Optional[Owner], float]]:
    """Device seconds per owner (None: no owner, or not in the table) of
    per-op seconds from a profile of ``module``; None without its table."""
    t = table(module)
    if t is None:
        return None
    out: Dict[Optional[Owner], float] = {}
    for op, s in op_seconds.items():
        o = t.get(instruction_name(op))
        out[o] = out.get(o, 0.0) + s
    return out


def profile_seconds(path: str, module: str
                    ) -> Optional[Dict[Optional[Owner], float]]:
    """``attribute`` over a profiler capture (an ``.xplane.pb``, or the
    newest under a directory): the ops of every device's ``XLA Ops``
    line, summed over devices."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    seconds: Dict[str, float] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                seconds[ev.name] = (seconds.get(ev.name, 0.0)
                                    + ev.duration_ns * 1e-9)
    return attribute(seconds, module)
