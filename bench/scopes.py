"""Device time by the program's ``rp.*`` scopes.

The program traces each phase of its step under a ``jax.named_scope``
(``src/repro/runtime/spans.py``: ``rp.ring``, ``rp.slot.F`` ...).  A scope is
metadata: it reaches each compiled instruction's ``op_name``, and an op
belongs to the innermost ``rp.*`` name there, seen through a vjp's wrappers
(``transpose(jvp(rp.merge))`` is ``rp.merge``); what XLA makes on its own
carries no ``op_name`` and takes a scope from its neighbours, which is
logged apart as "inferred" (:func:`scope_map`).  The profiler's ops are the
compiled step's instructions (``bench/trace.py``), so the compiled module
text maps each op of the trace to its scope.

:func:`attach` builds the cell's step once more after the window, with the
same calls as the run (``Program``, the state's shapes, a batch), and
compiles it under a persistent-cache key that holds the module's metadata:
the run's own key leaves ``op_name`` out, so an entry written by a build
without these scopes would answer with its own names.  The first traced run
in a cache directory pays a full compile for it (about 50 s at Qwen3-1.7B on
a v5e); later ones load the entry.  XLA compiles the same module to the same
instructions, so the names match the trace's.  It maps the trace's op
seconds to scopes and adds to the reduced trace:

  scopes      {HLO op name: its rp.* scope, or ""}
  scopes_s    {scope: device seconds of its ops}
  inferred_s  {scope: the part of scopes_s whose ops took a neighbour's scope}
  unscoped_s  device seconds of the ops with no rp.* scope

``scopes_s`` plus ``unscoped_s`` is the summed op time ``ops_s``.  It logs one
line per scope to standard error.  Where the program has no ``rp.*`` scope
at all, the metrics that read a scope read nothing.
"""
from __future__ import annotations

import contextlib
import math
import re
import sys
from collections import Counter

SCOPE = re.compile(r"rp\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.-]+) = (.*)$")
OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.-]+)|"
                    r"branch_computations=\{([^}]*)\}")
OP_NAME = re.compile(r'op_name="([^"]*)"')
TOP_OPS = 10                      # unscoped and inferred ops logged


def innermost(op_name: str) -> str:
    """The innermost ``rp.*`` scope of an ``op_name``, or ""."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else ""


def _operands(rest: str, start: int, known: set) -> list:
    """Names of the instructions in the operand list that opens at
    ``rest[start]``."""
    depth, i = 1, start + 1
    while i < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    return [t for t in re.findall(r"[\w.-]+", rest[start + 1:i - 1])
            if t in known]


def _instructions(hlo_text: str) -> dict:
    """``{name: (computation, opcode, own scope, operands, called
    computations, is root)}`` of a module's text."""
    out, comp, known = {}, None, set()
    for line in hlo_text.splitlines():
        if line.startswith("}"):
            comp, known = None, set()
            continue
        m = INSTRUCTION.match(line)
        if m and comp is not None:
            root, name, rest = m.groups()
            op = OPCODE.search(rest)
            kind = op.group(1) if op else ""
            own = OP_NAME.search(rest)
            called = [c for a, b in CALLED.findall(rest)
                      for c in ([a] if a else re.findall(r"[\w.-]+", b))]
            out[name] = (comp, kind, innermost(own.group(1)) if own else "",
                         _operands(rest, op.start(1) + len(kind), known)
                         if op else [], called, bool(root))
            known.add(name)
        elif not line.startswith(" "):
            h = HEADER.match(line)
            comp = h.group(1) if h else None
    return out


def scope_map(hlo_text: str) -> tuple[dict, dict, set]:
    """``({instruction: scope or ""}, {instruction: opcode}, {instructions
    whose scope is inferred})`` of every instruction of a compiled module's
    text.

    An instruction's scope is the innermost ``rp.*`` name of its own
    ``op_name``.  Instructions that XLA makes (layout copies, broadcast
    clones, loops it builds for a scatter, fusions whose root it made)
    carry none, so one without takes, in this order: for a fusion, the
    scope most of its fused instructions carry; the scope its consumers
    carry, where they agree (a loop body's root counts its loop as a
    consumer; unscoped consumers are waited for, and left out only once
    nothing more settles); the scope of the loop or branch whose body
    holds it.  What none of these reaches, or what consumers of two
    scopes share, stays unscoped ("")."""
    ins = _instructions(hlo_text)
    scope = {n: i[2] for n, i in ins.items()}
    caller, users, votes = {}, {}, {}
    for n, (comp, kind, own, operands, called, _) in ins.items():
        for c in called:
            caller.setdefault(c, n)
        for o in operands:
            users.setdefault(o, []).append(n)
        if own:
            votes.setdefault(comp, Counter())[own] += 1
    for n, (_, kind, own, _, called, _) in ins.items():
        if kind == "fusion" and not own and called and votes.get(called[0]):
            scope[n] = votes[called[0]].most_common(1)[0][0]
    for strict in (True, False):
        changed = True
        while changed:
            changed = False
            for n, (comp, _, _, _, _, root) in ins.items():
                if scope[n] or strict and any(
                        not scope[u] for u in users.get(n, ())):
                    continue
                found = {scope[u] for u in users.get(n, ()) if scope[u]}
                host = caller.get(comp)
                if root and host and scope[host]:
                    found.add(scope[host])
                if not found and host and ins[host][1] != "fusion" \
                        and scope[host]:
                    found = {scope[host]}
                if len(found) == 1:
                    scope[n] = found.pop()
                    changed = True
    return (scope, {n: i[1] for n, i in ins.items()},
            {n for n, i in ins.items() if scope[n] and not i[2]})


def split(ops_s: dict, scopes: dict) -> tuple[dict, float]:
    """Op seconds by scope, and the seconds of the ops with none (or not in
    the map)."""
    members: dict = {}
    for op, s in ops_s.items():
        members.setdefault(scopes.get(op, ""), []).append(s)
    unscoped = members.pop("", [])
    return ({sc: math.fsum(v) for sc, v in sorted(members.items())},
            math.fsum(unscoped))


@contextlib.contextmanager
def _metadata_keyed():
    """Key JAX's persistent compilation cache on the module's metadata too
    (``op_name`` included) while open."""
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, was)


def compile_step(cell: dict, seed: int) -> str:
    """The compiled step's module text, built as the run builds it."""
    import jax

    from bench import harness, traffic
    prog = harness.Program(cell)
    state = jax.eval_shape(prog.init, harness.seed_key(seed))
    batch = traffic.Batches(cell["mix"], prog.cfg.vocab_size, seed).batch(0)
    with prog.mesh:
        lowered = prog.step.lower(state, batch)
        with _metadata_keyed():
            return lowered.compile().as_text()


def attach(record: dict, trace: dict, log=None) -> dict:
    """Add the scope numbers to a reduced trace (once per run) and log
    them; returns the trace."""
    if "scopes_s" in trace:
        return trace
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    scopes, kinds, inferred = scope_map(
        compile_step(record["cell"], record["seed"]))
    add(trace, scopes, inferred)
    for line in describe(trace, kinds):
        log(line)
    return trace


def add(trace: dict, scopes: dict, inferred=()) -> dict:
    """Add a scope map, the set of its inferred entries, and what they give
    to a reduced trace."""
    scopes_s, unscoped_s = split(trace["ops_s"], scopes)
    inferred_s, _ = split({op: s for op, s in trace["ops_s"].items()
                           if op in inferred}, scopes)
    trace.update(scopes=scopes, inferred=set(inferred), scopes_s=scopes_s,
                 inferred_s=inferred_s, unscoped_s=unscoped_s)
    return trace


def describe(trace: dict, kinds: dict) -> list:
    """Lines for standard error: each scope's ms per step, its share of the
    op time and the part of it inferred; the inferred and the unscoped ops
    that took most time; the op time per step beside the busy time."""
    steps = max(trace["steps"], 1)
    ms = lambda s: f"{s / steps * 1e3:.3f}"
    total = math.fsum(trace["ops_s"].values())
    share = (lambda s: 100.0 * s / total) if total > 0 else (lambda s: 0.0)
    lines = [f"scope {sc} {ms(s)} ms/step {share(s):.2f}% (inferred "
             f"{ms(trace['inferred_s'].get(sc, 0.0))})"
             for sc, s in sorted(trace["scopes_s"].items(),
                                 key=lambda kv: -kv[1])]
    lines.append(f"scope (none) {ms(trace['unscoped_s'])} ms/step "
                 f"{share(trace['unscoped_s']):.2f}%")
    by_time = lambda ops: sorted((op for op in ops if op in trace["ops_s"]),
                                 key=lambda op: -trace["ops_s"][op])[:TOP_OPS]
    lines += [f"inferred {op} {kinds.get(op, '?')} {trace['scopes'][op]} "
              f"{ms(trace['ops_s'][op])} ms/step"
              for op in by_time(trace["inferred"])]
    lines += [f"unscoped {op} {kinds.get(op, '?')} "
              f"{ms(trace['ops_s'][op])} ms/step"
              for op in by_time(op for op, sc in trace["scopes"].items()
                                if not sc)]
    missing = [op for op in trace["ops_s"] if op not in trace["scopes"]]
    lines.append(f"ops {ms(total)} ms/step, busy {ms(trace['busy_s'])} "
                 f"ms/step; {len(missing)} traced op name(s) not in the "
                 f"module")
    return lines


def ms_per_step(record: dict, trace: dict | None, names) -> float | None:
    """Device ms per step of the ops filed under one of ``names``; nothing
    where no instruction of the program is (a program without these
    scopes, or a rename)."""
    if trace is None or trace.get("steps", 0) < 1:
        return None
    attach(record, trace)
    if not set(names) & set(trace["scopes"].values()):
        return None
    seconds = math.fsum(trace["scopes_s"].get(n, 0.0) for n in names)
    return seconds / trace["steps"] * 1e3
