"""Device time per section of the program, from the trace and the step's
compiled HLO.

The program names its sections with ``jax.named_scope``: ``vit`` and ``lm``
(MLLM), ``teacher`` and ``student`` (distillation), ``grad_accum`` and
``optimizer`` (both), and ``flash_bwd`` inside whichever section runs the
flash attention backward.  XLA keeps each name in the compiled program, in
the ``op_name`` of an instruction's metadata, under the wrappers of JAX's
transforms (``transpose(jvp(lm))``, ``checkpoint``, ``while/body``, ...).
The trace's device ops are named by those same HLO instructions
(``%fusion.1411``), so each op can be put down to the scopes it ran in;
an instruction XLA left without metadata takes the path of what it fuses
or copies (``op_scopes``).

The compiled text is taken once per run, after the window: the step is
lowered on the job's live state and a freshly prepared batch, as its own
``dispatch`` would call it, and compiled (the persistent compilation cache
holds it).  A program without the scopes reads nothing: each reading is
then ``None``.
"""
from __future__ import annotations

import itertools
import re

from benchmarks.chip import trace

#: the scopes that split a step into sections; ``flash_bwd`` runs inside one
SECTIONS = ("vit", "lm", "teacher", "student", "grad_accum", "optimizer")
#: path components that only say how JAX staged the op, never where
_STAGING = {"checkpoint", "closed_call", "while", "body", "cond"}
_WRAPPER = re.compile(r"[A-Za-z_]\w*\((.*)\)")
_COMPUTATION = re.compile(r"(?:ENTRY )?%(\S+) ")
_INSTR = re.compile(r"\s+(?:ROOT )?%([^\s=]+) = (.*)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,)]+)")
_OPERAND = re.compile(r"\(%([^\s,)]+)")


def components(op_name: str) -> tuple:
    """The path components of an ``op_name``, with the transform wrappers
    stripped (``transpose(jvp(lm))`` is ``lm``) and the staging ones
    (``checkpoint``, ``remat*``, ``closed_call``, ``while``, ``body``,
    ``cond``) dropped."""
    out = []
    for c in op_name.split("/"):
        while (m := _WRAPPER.fullmatch(c)) is not None:
            c = m.group(1)
        if c and c not in _STAGING and not c.startswith("remat"):
            out.append(c)
    return tuple(out)


def op_scopes(hlo_text: str) -> dict:
    """{HLO instruction name (without ``%``): its ``op_name``'s path
    components} for every instruction of ``hlo_text`` that has one.

    XLA leaves some instructions without metadata.  A fusion of them (a
    multi-output fusion, say) takes the path its fused instructions share
    (the longest common prefix of theirs), and any other (an async copy,
    say) the path of its first operand."""
    own, calls, operand, body = {}, {}, {}, {}
    comp: list = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line[:1] in ("%", "E") and (c := _COMPUTATION.match(line)):
                comp = body.setdefault(c.group(1), [])
            continue
        name, rest = m.groups()
        comp.append(name)
        if (op := _OP_NAME.search(rest)) is not None:
            own[name] = components(op.group(1))
        elif (c := _CALLS.search(rest)) is not None:
            calls[name] = c.group(1)
        elif (o := _OPERAND.search(rest)) is not None:
            operand[name] = o.group(1)
    out = dict(own)
    for name, called in calls.items():
        paths = [own[i] for i in body.get(called, ()) if i in own]
        if paths:
            out[name] = tuple(c[0] for c in itertools.takewhile(
                lambda c: len(set(c)) == 1, zip(*paths)))
    for name in operand:
        seen, cur = {name}, operand[name]
        while cur not in out and cur in operand and cur not in seen:
            seen.add(cur)
            cur = operand[cur]
        if cur in out:
            out[name] = out[cur]
    return out


class _Call(Exception):
    """Carries the arguments of the step call that ``dispatch`` made."""


def lowered(job):
    """``job.step`` lowered on the job's live state and a freshly prepared
    batch: the call its ``dispatch`` makes, caught before it runs, so the
    state is left as it was."""
    step = job.step

    def catch(*args):
        raise _Call(*args)

    job.step = catch
    try:
        job.dispatch(job.prepare(0), 0)
    except _Call as call:
        return step.lower(*call.args)
    finally:
        job.step = step
    raise RuntimeError("the path's dispatch did not call its step")


def _ops(run) -> dict:
    """{device: [(path components, start, end, instruction)]} of the
    window's leaf ops, named from the run's step, compiled once per
    run."""
    if getattr(run, "scoped_ops", None) is None:
        names = op_scopes(lowered(run.job).compile().as_text())
        run.scoped_ops = {
            d: [(names.get(_instruction(n), ()), s, e, _instruction(n))
                for n, s, e in trace.leaves(run.trace.clipped(d))]
            for d in run.trace.ops}
        _log_unscoped(run)
    return run.scoped_ops


def _instruction(event: str) -> str:
    return event.split(" = ")[0].lstrip("%")


def _union_ns(run, keep) -> float:
    """Nanoseconds of the union of the leaf ops whose path components
    ``keep`` accepts, summed over devices."""
    return sum(sum(e - s for s, e in trace.union(
        (s, e) for c, s, e, _ in ops if keep(c)))
        for ops in _ops(run).values())


def scope_ms(run, scope: str):
    """Milliseconds per measured step in which the device ran an op of
    ``scope``: the union of the window's leaf ops whose path holds it
    (async copies overlap compute, and an overlap counts once), summed
    over devices, over chips and steps; ``None`` where no op holds it."""
    if not any(scope in c for ops in _ops(run).values()
               for c, _, _, _ in ops):
        return None
    return (_union_ns(run, lambda c: scope in c) / 1e6 / run.chips
            / len(run.steps))


def _sectioned(c) -> bool:
    return any(s in c for s in SECTIONS)


def unscoped_share(run) -> float:
    """The share of busy device time in which no op of any section ran:
    what the sections' union leaves of the busy time (ops with no section
    scope, and the time between a loop's ops)."""
    busy = sum(run.trace.busy_ns(d) for d in run.trace.ops)
    return 1.0 - _union_ns(run, _sectioned) / busy


def top_unscoped(run, k: int = 10) -> list:
    """[[instruction, ms per step]] of the ``k`` leaf ops with no section
    scope that took most device time in the window, over every device."""
    tot: dict = {}
    for ops in _ops(run).values():
        for c, s, e, op in ops:
            if not _sectioned(c):
                tot[op] = tot.get(op, 0.0) + (e - s) / 1e6 / len(run.steps)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def _log_unscoped(run):
    busy_ms = run.trace.busy_s() * 1e3 / len(run.steps)
    run.log(f"scopes: busy {busy_ms!r} ms a step, unscoped share "
            f"{unscoped_share(run)!r}, top unscoped ops {top_unscoped(run)}")
